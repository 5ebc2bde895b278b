"""A rehearsal run of each cell prints a well-formed last line, and
``correct`` comes out false when a guarantee is broken: a parity shard
or a checksum flipped in a store, the program encoding with a wrong
generator row (the control: the nearest thing to a lower precision that
a system with exact arithmetic has), and the timed path's own kernel
wrapper altering a parity byte where it is produced."""

import re

import pytest

from benchmark import files

from .helpers import run_cell

CELLS = [w["name"] for w in files.benchmark_json()["workloads"]]

FLIP_PARITY = '''
import benchmark.check as C
from ceph_tpu.store.transaction import Transaction
_check = C.check
def check(cluster, gen, config, seed, count):
    oid = gen.oid(C.sample_objects(gen, seed, count)[0])
    acting = cluster.mon.osdmap.object_to_acting(cluster.pool, oid)
    shard = next(s for s in (9, 10, 11, 8) if acting[s] >= 0)
    store = cluster.stores[acting[shard]]
    key = C._shard_keys(store)[(oid, shard)]
    byte = bytes([store.read(key)[100] ^ 1])
    store.queue_transactions(Transaction().write(key, 100, byte))
    return _check(cluster, gen, config, seed, count)
C.check = check
'''

FLIP_CSUM = '''
import json
import benchmark.check as C
from ceph_tpu.store.transaction import Transaction
_check = C.check
def check(cluster, gen, config, seed, count):
    oid = gen.oid(C.sample_objects(gen, seed, count)[0])
    acting = cluster.mon.osdmap.object_to_acting(cluster.pool, oid)
    store = cluster.stores[acting[2]]
    key = C._shard_keys(store)[(oid, 2)]
    hinfo = json.loads(store.getattr(key, C.HINFO_ATTR))
    hinfo["hashes"][5] ^= 1
    store.queue_transactions(Transaction().setattr(
        key, C.HINFO_ATTR, json.dumps(hinfo).encode()
    ))
    return _check(cluster, gen, config, seed, count)
C.check = check
'''

WRONG_ROW = '''
import ceph_tpu.gf as G, ceph_tpu.gf.matrices as GM
_van = GM.vandermonde_rs_matrix
def wrong(k, m):
    mat = _van(k, m).copy()
    mat[k + 1, 3] ^= 0x1D
    return mat
for mod in (G, GM):
    mod.vandermonde_rs_matrix = wrong
import ceph_tpu.codecs.jerasure as J
if hasattr(J, "vandermonde_rs_matrix"):
    J.vandermonde_rs_matrix = wrong
'''

KERNEL_ALTERS_PARITY = '''
import ceph_tpu.ops.pallas_encode as PE
_fused = PE.gf_encode_csum_bitplane_pallas
def altered(*a, **k):
    parity, csums = _fused(*a, **k)
    return parity.at[0, 0, 7].set(parity[0, 0, 7] ^ 1), csums
PE.gf_encode_csum_bitplane_pallas = altered
'''


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_prints_a_well_formed_last_line(cell, trace):
    chips = files.cell(cell)["chips"]
    code, last, out, _took = run_cell(cell, trace=trace, devices=chips)
    assert code == 0, out
    assert last is not None, out
    assert set(last) >= {"correct", "attempted", "failed", "metrics", "device"}
    assert last["correct"] is True and last["failed"] == 0
    assert last["attempted"] > 0
    # a rehearsal names the CPU and carries no metric of a device
    assert last["device"]["platform"] == "cpu"
    assert last["metrics"] == {}
    assert "check: read_mismatch=0/limit 0" in out


def test_without_rehearse_a_cpu_is_refused_and_nothing_is_printed():
    code, last, out, _took = run_cell("rs84-4m.write", rehearse=False)
    assert code != 0
    assert last is None
    assert "a TPU is required" in out


def check_numbers(out: str) -> dict[str, int]:
    """The numbers the run compared, from its ``check:`` line."""
    line = next(ln for ln in out.splitlines() if "] check: " in ln)
    return {k: int(v) for k, v in re.findall(r"(\w+)=(\d+)/limit 0", line)}


@pytest.mark.parametrize("name,prelude,number", [
    ("parity flipped in a store", FLIP_PARITY, "shard_mismatch"),
    ("checksum flipped in a store", FLIP_CSUM, "csum_mismatch"),
    ("control: wrong generator row", WRONG_ROW, "shard_mismatch"),
    ("kernel alters a parity byte", KERNEL_ALTERS_PARITY, "shard_mismatch"),
])
def test_correct_turns_false(name, prelude, number):
    code, last, out, _took = run_cell("rs84-4m.write", prelude=prelude)
    assert last is not None, out
    assert last["correct"] is False, out
    assert code != 0
    numbers = check_numbers(out)
    assert numbers[number] > 0, numbers
    # what was not broken still compares equal
    assert numbers["read_mismatch"] == 0 and numbers["ledger_gap"] == 0
