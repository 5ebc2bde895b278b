"""The RBD pool with a disk down (``rs84-rbd-degraded``): its files
load, the cell rehearses ``correct`` on the CPU with one OSD down, the
five new metrics and the ten copies read on a traced rehearsal, a
program without the new counters reports none of the five, and the
pool's two controls (``control_degraded_rmw.py``) turn ``correct``
false at rehearsal size."""

from __future__ import annotations

import fnmatch

import pytest

from benchmark import files, metrics

from . import control_degraded_rmw
from .helpers import (
    PRINT_COUNTER_NAMES, counters_and_readings, run_cell,
)

CONFIG = "rs84-rbd-degraded"
CELL = "rs84-rbd-degraded.randwrite"
NEW = [
    "rmw_reconstruct_pct", "rmw_reconstruct_ms", "rmw_subreads_per_op",
    "hole_shards_per_write", "eagain_per_op",
]
COPIED = [
    "rmw_read_ms", "parity_delta_pct", "delta_apply_ms",
    "delta_ops_per_dispatch", "coalesced_op_pct", "write_p95_ms",
    "ec_write_encode_ms", "ec_write_fanout_ms", "subop_wait_ms",
    "store_read_ms",
]


def check_numbers(last: dict) -> dict:
    return {
        name: row["value"] for name, row in last["checked"].items()
        if "limit" in row
    }


def test_the_files_load_and_say_what_the_issue_gave():
    config, cell = files.config(CONFIG), files.cell(CELL)
    healthy = files.config("rs84-rbd")
    assert config["pool"] == healthy["pool"]
    assert config["cluster"] == healthy["cluster"]
    assert config["object_size"] == 4194304 and config["queue_depth"] == 32
    assert "reference" not in config["pool"]  # rs_vandermonde
    assert files.reference(config).name == "rs_vandermonde"
    assert set(config["reduced"]) == set(healthy["reduced"]) | {
        "backfill_target"
    }
    assert any("while the OSD is still down" in g for g in config["guarantees"])
    assert cell["traffic"] == "randwrite" and cell["chips"] == 1
    assert cell["preload_objects"] == 128 and cell["check_objects"] == 16
    assert cell["standing_fault"] == {"kill": "most_primary_osd"}
    assert cell["client"] == {"op_timeout_s": 15.0, "max_attempts": 2}
    assert cell["warmup"] == {
        "min_ops": 64, "quiet_s": 2.0,
        "moved": ["ec_dispatch:pallas_delta_bytes"],
    }
    assert cell["codec_kernel"] == files.cell("rs84-rbd.randwrite")[
        "codec_kernel"
    ]
    entry = next(
        w for w in files.benchmark_json()["workloads"] if w["name"] == CELL
    )
    assert entry["why"] == cell["why"] and len(cell["why"]) <= 200


def test_the_copies_read_what_their_originals_read():
    own = [m["name"] for m in files.metrics_for(CELL, "per_layer")]
    for name in NEW:
        assert name in own
        assert files.metric(name)["reader"] == "counter_ratio_of"
    for name in COPIED:
        copy, original = files.metric(f"{name}.{CONFIG}"), files.metric(name)
        assert copy.pop("name") == f"{name}.{CONFIG}" in own
        original.pop("name")
        assert copy == original


def test_a_program_without_the_counters_reports_none_of_the_five():
    """The parent: it has ``rmw_read_ops``, ``encode_ops`` and
    ``op_completed``, and none of the numerators."""
    moved = {
        "osd.1.loadpool.2.rmw:rmw_read_ops": 40,
        "osd.1.loadpool.2.rmw:rmw_read_seconds": 2.0,
        "osd.1.loadpool.2.rmw:encode_ops": 40,
        "loadgen_client:op_completed": 40,
    }
    ctx = metrics.RunContext(
        cell=files.cell(CELL), config=files.config(CONFIG),
        device_kind="TPU v5 lite", moved=moved, compiles=[], trace=None,
        window_s=30.0,
    )
    for name in NEW:
        assert metrics.read(files.metric(name), ctx) is None, name
    assert metrics.read(
        files.metric(f"rmw_read_ms.{CONFIG}"), ctx
    ) == pytest.approx(50.0)
    # and with them: the ratios they are
    ctx.moved = {
        **moved,
        "osd.1.loadpool.2.rmw:rmw_reconstruct_ops": 5,
        "osd.1.loadpool.2.rmw:rmw_reconstruct_seconds": 0.5,
        "osd.1.loadpool.2.rmw:rmw_subreads": 220,
        "osd.1.loadpool.2.rmw:hole_shard_writes": 40,
        "osd.1.eagain:not_primary": 1,
        "osd.2.eagain:window_unsettled": 3,
    }
    want = {
        "rmw_reconstruct_pct": 12.5, "rmw_reconstruct_ms": 100.0,
        "rmw_subreads_per_op": 5.5, "hole_shards_per_write": 1.0,
        "eagain_per_op": 0.1,
    }
    for name, value in want.items():
        assert metrics.read(files.metric(name), ctx) == pytest.approx(value)


@pytest.fixture(scope="module")
def traced_rehearsal():
    code, last, out, _took = run_cell(
        CELL, trace=1, prelude=PRINT_COUNTER_NAMES
    )
    assert code == 0 and last is not None and last["correct"], out
    return last, out


def test_the_cell_rehearses_correct_with_one_osd_down(traced_rehearsal):
    last, out = traced_rehearsal
    assert last["failed"] == 0 and last["attempted"] > 0
    numbers = check_numbers(last)
    assert not any(numbers.values()), numbers
    checked = last["checked"]
    assert checked["objects"]["value"] == 4
    assert checked["shards"]["value"] == 4 * 11  # the hole is not served
    assert "down for the whole run" in out


def test_every_new_metric_is_in_a_traced_rehearsal(traced_rehearsal):
    _last, out = traced_rehearsal
    names, readings = counters_and_readings(out)
    for name in NEW + [f"{name}.{CONFIG}" for name in COPIED]:
        assert name in readings, (name, sorted(readings))
    assert readings["eagain_per_op"] == 0
    # every write left one transaction unbuilt (ops on the window's
    # edges count on one side only)
    assert readings["hole_shards_per_write"] == pytest.approx(1.0, abs=0.1)
    assert 0 <= readings["rmw_reconstruct_pct"] < 50
    for pattern in (
        "osd.*.rmw:rmw_reconstruct_ops", "osd.*.rmw:rmw_reconstruct_seconds",
        "osd.*.rmw:rmw_subreads", "osd.*.rmw:hole_shard_writes",
        "osd.*.eagain:window_unsettled", "osd.*.opq:req_poll_holds",
    ):
        assert fnmatch.filter(names, pattern), pattern


@pytest.mark.parametrize("name", sorted(control_degraded_rmw.BREAKS))
def test_the_controls_turn_correct_false(name):
    code, last, out, _took = run_cell(
        CELL, prelude=control_degraded_rmw.BREAKS[name]
    )
    assert last is not None, out
    assert last["correct"] is False and code != 0, out
    numbers = check_numbers(last)
    assert numbers["shard_mismatch"] >= 1, numbers
    # every op was acknowledged: only what is stored is wrong
    assert last["failed"] == 0
