"""The accepted cells' traffic did not move: for each pair of mix and
size that a cell of PR 31's benchmark sends, on two seeds, the first
200 ops (class, kind, object, bytes, offset, sha1 of the payload, op on
the wire) equal what the parent's generator gave, recorded by
``golden.py`` before PR 32 touched the generator."""

import json
import os

import pytest

from . import golden

with open(
    os.path.join(os.path.dirname(__file__), "golden_traffic.json"),
    encoding="utf-8",
) as _f:
    RECORDED = json.load(_f)


def test_every_accepted_pair_of_mix_and_size_is_recorded():
    from benchmark import files

    sent = set()
    for cell in ("rs84-4m.write", "rs84-4m.degraded-read",
                 "rs84-4m-mesh4.write", "rs84-rbd.randwrite",
                 "rs84-64k.write"):
        spec = files.cell(cell)
        config = files.config(spec["config"])
        sent.add((spec["traffic"], config["object_size"],
                  config["queue_depth"], spec["preload_objects"]))
    assert sent == set(golden.CASES.values())
    assert set(RECORDED) == set(golden.CASES)


@pytest.mark.parametrize("seed", golden.SEEDS)
@pytest.mark.parametrize("case", sorted(golden.CASES))
def test_the_generator_gives_the_parents_ops(case, seed):
    want = RECORDED[case][str(seed)]
    got = golden.record_case(case, seed)
    assert len(got["ops"]) == golden.OPS
    for i, (a, b) in enumerate(zip(got["ops"], want["ops"])):
        assert a == b, f"op {i}: {a} != {b}"
    assert got.get("preload_sha1") == want.get("preload_sha1")
