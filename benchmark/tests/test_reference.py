"""The plain reference is pinned to the repo's golden corpus (read
only) and to crc32c's published check value."""

import glob
import json
import os

import numpy as np
import pytest

from benchmark.reference import crc32c, gf256, rs_vandermonde

from .helpers import ROOT

CORPUS = sorted(glob.glob(os.path.join(
    ROOT, "tests", "corpus", "*", "jerasure", "*technique=reed_sol_van"
)))


def test_corpus_has_reed_sol_van_entries():
    assert len(CORPUS) >= 3


@pytest.mark.parametrize("entry", CORPUS, ids=os.path.basename)
def test_encode_equals_corpus_chunks(entry):
    with open(os.path.join(entry, "profile.json")) as f:
        profile = json.load(f)["profile"]
    k, m = int(profile["k"]), int(profile["m"])
    with open(os.path.join(entry, "payload.bin"), "rb") as f:
        payload = f.read()
    with open(os.path.join(entry, "chunk.0"), "rb") as f:
        chunk_size = len(f.read())
    shards = rs_vandermonde.shards_of(payload, k, m, chunk_size)
    for i in range(k + m):
        with open(os.path.join(entry, f"chunk.{i}"), "rb") as f:
            assert shards[i].tobytes() == f.read(), f"chunk {i}"


@pytest.mark.parametrize("lost", [(0,), (3, 9), (0, 1, 2, 3), (8, 9, 10, 11)])
def test_any_k_shards_rebuild_the_object(lost):
    obj = np.random.default_rng(5).integers(0, 256, 3 * 32768 - 17, np.uint8)
    shards = rs_vandermonde.shards_of(obj.tobytes(), 8, 4, 4096)
    have = {i: shards[i] for i in range(12) if i not in lost}
    data = rs_vandermonde.decode_data(have, 8, 4)
    assert rs_vandermonde.object_from_data_shards(
        data, obj.size, 4096
    ) == obj.tobytes()


def test_field_is_a_field():
    for a in (1, 2, 3, 0x53, 0xCA, 255):
        assert gf256.mul(a, gf256.inv(a)) == 1
    assert gf256.mul(0x53, 0xCA) == gf256.mul(0xCA, 0x53)
    mat = rs_vandermonde.coding_matrix(8, 4)
    gen = np.concatenate([np.eye(8, dtype=np.uint8), mat])
    sub = gen[[0, 2, 4, 6, 8, 9, 10, 11]]
    ident = gf256.apply_matrix(gf256.invert(sub), sub)
    assert np.array_equal(ident, np.eye(8, dtype=np.uint8))


def test_crc32c_check_value():
    # the catalogue's check value is with the final inversion; the
    # register Ceph keeps is without it
    assert crc32c.crc32c(0xFFFFFFFF, b"123456789") ^ 0xFFFFFFFF == 0xE3069283
    rows = np.frombuffer(b"123456789abcdefghi", np.uint8).reshape(2, 9)
    got = crc32c.crc32c_rows(0xFFFFFFFF, rows)
    assert int(got[0]) == crc32c.crc32c(0xFFFFFFFF, b"123456789")
    assert int(got[1]) == crc32c.crc32c(0xFFFFFFFF, b"abcdefghi")


def test_crc32c_chains_block_by_block():
    data = np.random.default_rng(1).integers(0, 256, 3 * 4096, np.uint8)
    whole = crc32c.crc32c(0xFFFFFFFF, data.tobytes())
    crc = 0xFFFFFFFF
    for start in range(0, data.size, 4096):
        crc = crc32c.crc32c(crc, data[start:start + 4096].tobytes())
    assert crc == whole


def test_reference_imports_nothing_of_the_program():
    folder = os.path.join(ROOT, "benchmark", "reference")
    for name in os.listdir(folder):
        if name.endswith(".py"):
            with open(os.path.join(folder, name)) as f:
                imports = [
                    ln for ln in f if ln.lstrip().startswith(("import ", "from "))
                ]
            assert not [ln for ln in imports if "ceph_tpu" in ln], name
            assert all(
                ln.split()[1].split(".")[0] in ("__future__", "numpy", "")
                for ln in imports
            ), (name, imports)
