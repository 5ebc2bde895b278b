"""A rehearsal run of each cell prints a well-formed last line, and
``correct`` comes out false when a guarantee is broken: a parity shard
or a checksum flipped in a store, the program encoding with a wrong
generator row (the control: the nearest thing to a lower precision that
a system with exact arithmetic has), and the timed path's own kernel
wrapper altering a parity byte where it is produced. For a cell whose
objects have a life (``rs84-64k.mixed``, PR 32): a removed shard's key
put back in a store, and the program restarting a shard's cumulative
crc where an append should carry it on."""

import pytest

from benchmark import files

from .helpers import queued_cells_listed, run_cell, with_queued_cells

#: the listed cells, and the queued ones (``queued_cells.json``)
CELLS = [
    w["name"]
    for w in with_queued_cells(files.benchmark_json())["workloads"]
]

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


LEFTOVER_SHARD = '''
import benchmark.check as C
from ceph_tpu.store.transaction import Transaction
_check = C.check
def check(cluster, gen, config, seed, count):
    oid = gen.oid(C.sample_deleted(gen, seed, count)[0])
    store = cluster.stores[3]
    pool_id = next(iter(C._shard_keys(store).values())).partition(":")[0]
    store.queue_transactions(
        Transaction().write(f"{pool_id}:{oid}#s5", 0, b"left behind")
    )
    return _check(cluster, gen, config, seed, count)
C.check = check
'''

REMOVE_DOES_NOTHING = '''
import ceph_tpu.cluster.osd_daemon as D
def _op_remove(self, pg, msg):
    """Acknowledge a remove and remove nothing."""
    if not self._object_exists(pg, msg.oid):
        return D.OSDOpReply(msg.tid, self.osdmap.epoch, error="enoent")
    return D.OSDOpReply(msg.tid, self.osdmap.epoch)
D.OSDDaemon._op_remove = _op_remove
'''

ZERO_CARRIED_CRC = '''
import ceph_tpu.pipeline.hashinfo as H
def restarting(fold):
    def folded(self, old_size, to_append, *rest):
        if old_size:  # an append onto what earlier ops hashed
            self.cumulative_shard_hashes[min(to_append)] = 0
        return fold(self, old_size, to_append, *rest)
    return folded
H.HashInfo.append = restarting(H.HashInfo.append)
H.HashInfo.append_block_csums = restarting(H.HashInfo.append_block_csums)
'''


@pytest.mark.usefixtures(queued_cells_listed.__name__)
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
    assert last["checked"]["read_mismatch"] == {"value": 0, "limit": 0}


def test_without_rehearse_a_cpu_is_refused_and_nothing_is_printed():
    code, last, out, _took = run_cell("rs84-4m.write", rehearse=False)
    assert code != 0
    assert last is None
    assert "a TPU is required" in out


def check_numbers(last: dict) -> dict[str, int]:
    """The numbers the run held to a limit, from its result line's last
    key, ``checked``: every limit is 0."""
    assert list(last)[-1] == "checked"
    held = {k: v for k, v in last["checked"].items() if "limit" in v}
    assert all(v["limit"] == 0 for v in held.values()), held
    return {k: v["value"] for k, v in held.items()}


@pytest.mark.parametrize("name,cell,prelude,number", [
    ("parity flipped in a store", "rs84-4m.write", FLIP_PARITY,
     "shard_mismatch"),
    ("checksum flipped in a store", "rs84-4m.write", FLIP_CSUM,
     "csum_mismatch"),
    ("control: wrong generator row", "rs84-4m.write", WRONG_ROW,
     "shard_mismatch"),
    ("kernel alters a parity byte", "rs84-4m.write", KERNEL_ALTERS_PARITY,
     "shard_mismatch"),
    ("control: wrong generator row, appends", "rs84-64k.mixed", WRONG_ROW,
     "shard_mismatch"),
    ("kernel alters a parity byte, appends", "rs84-64k.mixed",
     KERNEL_ALTERS_PARITY, "shard_mismatch"),
    ("control: a removed shard's key put back", "rs84-64k.mixed",
     LEFTOVER_SHARD, "shard_leftover"),
    ("control: a remove acknowledged and not done", "rs84-64k.mixed",
     REMOVE_DOES_NOTHING, "delete_visible"),
    ("control: the carried crc restarted", "rs84-64k.mixed",
     ZERO_CARRIED_CRC, "csum_mismatch"),
])
def test_correct_turns_false(name, cell, prelude, number):
    code, last, out, _took = run_cell(cell, prelude=prelude)
    assert last is not None, out
    assert last["correct"] is False, out
    assert code != 0
    numbers = check_numbers(last)
    assert numbers[number] > 0, numbers
    # what was not broken still compares equal
    assert numbers["read_mismatch"] == 0 and numbers["ledger_gap"] == 0
    if prelude is REMOVE_DOES_NOTHING:
        # every shard is still there, and an append that meant to make
        # the name again found the old object under it
        assert numbers["shard_leftover"] >= 12, numbers
    else:
        assert numbers["delete_visible"] == 0


def test_the_mixed_cell_compares_appended_and_deleted_objects():
    code, last, out, _took = run_cell("rs84-64k.mixed")
    assert code == 0 and last["correct"], out
    checked = last["checked"]
    for name in ("objects", "shards", "csum_objects", "deleted_objects"):
        assert checked[name]["value"] > 0, checked
    assert checked["deleted_objects"]["at_least"] == 1
    # every object sampled was created and only appended to since, so
    # each has to carry a checksum over its whole shard
    assert checked["csum_objects"]["value"] == checked["objects"]["value"]
    assert "latency ms, append" in out and "latency ms, delete" in out
