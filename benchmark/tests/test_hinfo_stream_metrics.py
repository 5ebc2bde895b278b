"""``hinfo_stream_ms`` and ``csum_calls_per_append`` (PR 48): what the
raw-bytes HashInfo append costs an op, and how many device checksum
calls one append makes (1.0 since PR 48, whatever k+m is; 12 before it
at k=8 m=4, by a program that could not count them). Two data files
for ``counter_ratio_of``, listed by the mesh cell, the one cell whose
writes get no csums from the encode kernel."""

import pytest

from benchmark import files, metrics

from .helpers import rehearsal_readings, run_cell

CELL = "rs84-4m-mesh4.write"
SPECS = {
    "hinfo_stream_ms": dict(
        unit="ms", numerator=["osd.*.rmw:hinfo_stream_seconds"],
        denominator=["loadgen_client:op_completed"], scale=1000.0,
    ),
    "csum_calls_per_append": dict(
        unit="ratio", numerator=["osd.*.rmw:hinfo_stream_calls"],
        denominator=["osd.*.rmw:hinfo_streams"], scale=1.0,
    ),
}


def context(moved: dict) -> metrics.RunContext:
    return metrics.RunContext(
        cell={}, config={}, device_kind="cpu", moved=moved, compiles=[],
        trace=None, window_s=1.0,
    )


@pytest.mark.parametrize("name", SPECS)
def test_file_agrees_with_its_entry(name):
    spec = files.metric(name)
    listed, = [
        m for m in files.benchmark_json()["per_layer"] if m["name"] == name
    ]
    for key in ("name", "unit", "better", "source", "layer", "moves"):
        assert spec[key] == listed[key], key
    assert (listed["unit"], listed["better"]) == (
        SPECS[name]["unit"], "lower"
    )
    assert (listed["layer"], listed["moves"], listed["source"]) == (
        "RMW pipeline", "client_mbs", "program_counter"
    )
    assert listed["workloads"] == [CELL]
    assert spec["reader"] == "counter_ratio_of"
    for key in ("numerator", "denominator", "scale"):
        assert spec[key] == SPECS[name][key], key
    assert listed in files.metrics_for(CELL, "per_layer")
    assert listed not in files.metrics_for("rs84-4m.write", "per_layer")


def test_the_new_entries_are_the_last_two():
    names = [m["name"] for m in files.benchmark_json()["per_layer"]]
    assert names[-2:] == list(SPECS)
    assert all(names.count(name) == 1 for name in SPECS)


#: one op worker's window on the chip as the change counts it, and the
#: parent's twelve calls an append had it had the counter
MOVED = {
    "loadgen_client:op_completed": 200.0,
    "osd.3.loadpool.1.rmw:hinfo_streams": 120.0,
    "osd.3.loadpool.1.rmw:hinfo_stream_calls": 120.0,
    "osd.3.loadpool.1.rmw:hinfo_stream_seconds": 2.4,
    "osd.4.loadpool.7.rmw:hinfo_streams": 80.0,
    "osd.4.loadpool.7.rmw:hinfo_stream_calls": 80.0,
    "osd.4.loadpool.7.rmw:hinfo_stream_seconds": 1.6,
    "osd.4.loadpool.7.rmw:hinfo_stream_bytes": 80.0 * 6291456,
}


@pytest.mark.parametrize("calls,want", [(1.0, 1.0), (12.0, 12.0), (0.0, 0.0)])
def test_they_read_recorded_counter_deltas(calls, want):
    moved = {
        k: v * calls if k.endswith("hinfo_stream_calls") else v
        for k, v in MOVED.items()
    }
    assert metrics.read(
        files.metric("csum_calls_per_append"), context(moved)
    ) == pytest.approx(want)
    assert metrics.read(
        files.metric("hinfo_stream_ms"), context(moved)
    ) == pytest.approx(20.0)


def test_a_window_without_a_raw_append_leaves_the_ratio_out():
    """The fused route's cells, were they listed: the counters are
    there and read 0, so the time an op is 0.0 and calls an append,
    0 over 0, is nothing."""
    moved = {k: 0.0 for k in MOVED}
    moved["loadgen_client:op_completed"] = 200.0
    assert metrics.read(
        files.metric("csum_calls_per_append"), context(moved)
    ) is None
    assert metrics.read(
        files.metric("hinfo_stream_ms"), context(moved)
    ) == 0.0


def test_a_program_without_the_counters_reads_nothing():
    """What the parent commit gives: no such counter, so no reading and
    no error, though everything the parent does count is moving."""
    moved = {
        "loadgen_client:op_completed": 200.0,
        "osd.3.loadpool.1.rmw:encode_ops": 200.0,
        "osd.3.loadpool.1.rmw:hinfo_folds": 0.0,
        "osd.3.loadpool.1.rmw:hinfo_fold_seconds": 0.0,
        "ec_dispatch:mesh_encode": 200.0,
    }
    for name in SPECS:
        assert metrics.read(files.metric(name), context(moved)) is None


def test_a_rehearsal_of_the_mesh_cell_reports_both():
    """On the CPU the cell's objects are 256 KiB, its shards 32 KiB:
    under ``csum_device_min_bytes``, so every append is the host's and
    calls an append read 0.0 (on the chip, 512 KiB shards, 1.0). Names
    and plumbing, never a time."""
    code, last, text, _took = run_cell(
        CELL, trace=1, devices=files.cell(CELL)["chips"]
    )
    assert code == 0 and last["correct"], text
    readings = rehearsal_readings(text)
    assert readings["csum_calls_per_append"] == 0.0
    assert readings["hinfo_stream_ms"] > 0
    assert readings["hinfo_fold_ms"] == 0.0
