"""The cell ``rs84-64k.write`` (PR 30) as data: its configuration and
cell files agree with their ``BENCHMARK.json`` entries and are
``rs84-4m``'s pool at 64 KiB and depth 64, its four ring metrics read
the counters they name, a program without those counters leaves them
out, and the cell rehearses ``correct`` on the CPU."""

import fnmatch

import pytest

from benchmark import files, metrics

from .helpers import PRINT_COUNTER_NAMES, counters_and_readings, run_cell

CELL, CONFIG = "rs84-64k.write", "rs84-64k"
RING_METRICS = [
    "ring_op_pct", "ring_ops_per_batch", "ring_pad_pct", "ring_wait_ms",
]


def entry(group: str, name: str) -> dict:
    return next(
        e for e in files.benchmark_json()[group] if e["name"] == name
    )


def test_the_configuration_is_the_flagship_pool_under_small_puts():
    config, base = files.config(CONFIG), files.config("rs84-4m")
    listed = entry("configs", CONFIG)
    assert listed["file"] == "benchmark/configs/rs84-64k.json"
    assert config["source"] == listed["source"]
    assert len(listed["source"]) <= 200
    assert "cosbench_64K_write.yaml" in listed["source"]
    assert set(config["reduced"]) == set(listed["reduced"]) == {
        "osd_hosts", "store", "working_set_objects", "scheduled_scrubs",
    }
    assert config["pool"] == base["pool"]
    assert config["cluster"] == base["cluster"]
    assert config["guarantees"] == base["guarantees"]
    assert (config["object_size"], config["queue_depth"]) == (65536, 64)
    # two whole stripes an object, 8 KiB a shard
    stripe = config["pool"]["k"] * config["pool"]["chunk_size"]
    assert config["object_size"] == 2 * stripe
    assert {"source_wording", "queue_depth", "bucket_index"} <= set(
        config["assumed"]
    )


def test_the_cell_is_one_chip_closed_loop_new_objects_only():
    cell, listed = files.cell(CELL), entry("workloads", CELL)
    for key in ("config", "traffic", "chips", "why"):
        assert cell[key] == listed[key], key
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "write", 1
    )
    assert len(cell["why"]) <= 200
    assert files.mix("write")["classes"] == [
        {"name": "seq_write", "op": "write_new", "weight": 1}
    ]
    assert cell["preload_objects"] == 0 and cell["standing_fault"] is None
    assert cell["warmup"] == {"min_ops": 256, "quiet_s": 2.0}
    assert cell["check_objects"] == 16 and cell["trace_window_s"] == 30
    assert cell["codec_kernel"] == {
        "match": "%_apply_tiled_csum", "csum": True
    }
    base = files.cell("rs84-4m.write")
    assert cell["deadlines_s"] == base["deadlines_s"]
    assert cell["client"] == base["client"]
    # whole stripes: check.py compares whole-chunk shards
    stripe = 8 * 4096
    assert cell["rehearse"]["object_size"] % stripe == 0
    assert cell["rehearse"]["pg_num"] == 8


def test_the_entries_are_listed_and_one_cell_takes_four_chips():
    """Membership, not position: every later PR appends."""
    b = files.benchmark_json()
    assert CONFIG in [c["name"] for c in b["configs"]]
    assert CELL in [w["name"] for w in b["workloads"]]
    assert set(RING_METRICS) <= {m["name"] for m in b["per_layer"]}
    assert sum(w["chips"] == 4 for w in b["workloads"]) == 1


@pytest.mark.parametrize("name", RING_METRICS)
def test_metric_file_agrees_with_its_entry(name):
    spec, listed = files.metric(name), entry("per_layer", name)
    for key in ("name", "unit", "better", "source", "layer", "moves"):
        assert spec[key] == listed[key], key
    assert CELL in listed["workloads"]
    assert listed["layer"] == "staging ring"
    assert listed["moves"] == "client_mbs"
    assert spec["reader"] == "counter_ratio"


def test_the_metric_files_read_a_recorded_counter_delta():
    """A window's counter deltas as the program's sets name them."""
    moved = {
        "ec_stream:ops": 660.0,
        "ec_stream:batches": 400.0,
        "ec_stream:fused_batches": 400.0,
        "ec_stream:fused_batch_ops": 660.0,
        "ec_stream:fused_batch_stripes": 1320.0,
        "ec_stream:fused_pad_stripes": 330.0,
        "ec_stream:ring_wait_seconds": 3.3,
        "loadgen_client:op_completed": 1000.0,
    }
    ctx = metrics.RunContext(
        cell={}, config={}, device_kind="cpu", moved=moved, compiles=[],
        trace=None, window_s=1.0,
    )
    want = {
        "ring_op_pct": 66.0, "ring_ops_per_batch": 1.65,
        "ring_pad_pct": 20.0, "ring_wait_ms": 5.0,
    }
    for name, value in want.items():
        assert metrics.read(files.metric(name), ctx) == pytest.approx(value)


def test_a_program_without_the_counters_does_not_raise():
    """What the parent commit gives in this cell: ``ec_stream:ops`` and
    none of this PR's counters. The batch metrics have nothing to
    divide by and are left out; nothing raises."""
    ctx = metrics.RunContext(
        cell={}, config={}, device_kind="cpu",
        moved={"loadgen_client:op_completed": 10.0, "ec_stream:ops": 6.0,
               "ec_stream:batches": 4.0},
        compiles=[], trace=None, window_s=1.0,
    )
    assert metrics.read(files.metric("ring_ops_per_batch"), ctx) is None
    assert metrics.read(files.metric("ring_pad_pct"), ctx) is None
    assert metrics.read(files.metric("ring_op_pct"), ctx) == pytest.approx(60.0)
    assert metrics.read(files.metric("ring_wait_ms"), ctx) == 0.0


@pytest.fixture(scope="module")
def rehearsal():
    """(counter names over the traced window, readings) of the cell."""
    code, last, text, _took = run_cell(
        CELL, trace=1, prelude=PRINT_COUNTER_NAMES
    )
    assert code == 0 and last["correct"], text
    return counters_and_readings(text)


@pytest.mark.parametrize("name", RING_METRICS)
def test_the_program_has_the_counters_and_the_cell_reads_them(name, rehearsal):
    names, readings = rehearsal
    spec = files.metric(name)
    for pattern in spec["numerator"] + spec["denominator"]:
        assert any(fnmatch.fnmatchcase(n, pattern) for n in names), (
            f"{name}: no counter matches {pattern!r}"
        )
    assert isinstance(readings.get(name), float), readings
    assert readings[name] >= 0


def test_the_rehearsal_is_correct_and_rides_the_ring(rehearsal):
    """``--rehearse`` ends ``correct`` on the CPU (the fixture) and
    ops ride the ring. (Off the chip a batch size compiles when it is
    first met, so a rehearsal's window may hold a compilation; on the
    chip the geometry's first batch compiles them all.)"""
    _names, readings = rehearsal
    assert readings["ring_op_pct"] > 10
    assert readings["ring_ops_per_batch"] >= 1


def test_untraced_rehearsal_ends_correct():
    code, last, text, _took = run_cell(CELL, trace=0)
    assert code == 0 and last["correct"], text
    assert last["failed"] == 0
