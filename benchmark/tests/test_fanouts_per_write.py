"""``fanouts_per_write`` (PR 27): fan-outs an encoded write costs, as a
data file for ``counter_ratio``. A ``writefull`` that shrinks nothing is
one fan-out; the write cells send nothing else, so they read 1.0 (2.0
on a program that follows every ``writefull`` with a truncate)."""

import json

import pytest

from benchmark import files, metrics

from .helpers import run_cell

NAME = "fanouts_per_write"
WRITE_CELLS = ["rs84-4m.write", "rs84-4m-mesh4.write"]


def context(moved: dict) -> metrics.RunContext:
    return metrics.RunContext(
        cell={}, config={}, device_kind="cpu", moved=moved, compiles=[],
        trace=None, window_s=1.0,
    )


def test_file_agrees_with_its_entry_and_is_the_last_one():
    spec = files.metric(NAME)
    listed = files.benchmark_json()["per_layer"][-1]
    for key in ("name", "unit", "better", "source", "layer", "moves"):
        assert spec[key] == listed[key], key
    assert (listed["unit"], listed["better"]) == ("ratio", "lower")
    assert listed["layer"] == "RMW pipeline"
    assert listed["workloads"] == WRITE_CELLS
    assert spec["reader"] == "counter_ratio"
    assert spec["numerator"] == [
        "osd.*.rmw:encode_ops", "osd.*.rmw:truncate_ops"
    ]
    assert spec["denominator"] == ["osd.*.rmw:encode_ops"]
    for cell in WRITE_CELLS:
        assert listed in files.metrics_for(cell, "per_layer")
    for cell in ("rs84-4m.degraded-read", "rs84-rbd.randwrite"):
        assert listed not in files.metrics_for(cell, "per_layer")


@pytest.mark.parametrize("truncates,want", [(0.0, 1.0), (9.0, 1.09),
                                            (100.0, 2.0)])
def test_it_reads_a_recorded_counter_delta(truncates, want):
    moved = {
        "osd.3.loadpool.1.rmw:encode_ops": 60.0,
        "osd.4.loadpool.7.rmw:encode_ops": 40.0,
        "osd.3.loadpool.1.rmw:write_ops": 60.0,
    }
    if truncates:
        moved["osd.4.loadpool.7.rmw:truncate_ops"] = truncates
    assert metrics.read(files.metric(NAME), context(moved)) == (
        pytest.approx(want)
    )


def test_a_window_without_an_encoded_write_leaves_it_out():
    moved = {"loadgen_client:op_completed": 10.0,
             "osd.3.loadpool.1.rmw:truncate_ops": 3.0,
             "osd.3.loadpool.1.read:read_ops": 10.0}
    assert metrics.read(files.metric(NAME), context(moved)) is None


@pytest.mark.parametrize("cell", WRITE_CELLS)
def test_a_write_cell_reads_one_fanout_a_write(cell):
    code, last, text, _took = run_cell(
        cell, trace=1, devices=files.cell(cell)["chips"]
    )
    assert code == 0 and last["correct"], text
    readings = json.loads(next(
        ln for ln in text.splitlines() if "rehearsal readings" in ln
    ).split("): ", 1)[1])["metrics"]
    assert readings[NAME] == 1.0
