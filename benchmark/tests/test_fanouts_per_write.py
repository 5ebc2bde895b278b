"""``fanouts_per_write`` (PR 27): fan-outs an encoded write costs, as a
data file for ``counter_ratio``. A ``writefull`` that shrinks nothing is
one fan-out; the cells that write send nothing that shrinks an object
(a patch and an append are one fan-out too), so they read 1.0 (2.0 on a
program that follows every ``writefull`` with a truncate)."""


import pytest

from benchmark import files, metrics

from .helpers import (
    QUEUED, queued_cells_listed, rehearsal_readings, run_cell,
)

NAME = "fanouts_per_write"
LISTED_CELLS = [
    "rs84-4m.write", "rs84-4m-mesh4.write", "rs84-rbd.randwrite",
    "rs84-64k.write",
]
#: and the queued cells that write (``queued_cells.json``)
WRITE_CELLS = LISTED_CELLS + [
    c for c, q in QUEUED.items() if NAME in q["per_layer_lists"]
]


def context(moved: dict) -> metrics.RunContext:
    return metrics.RunContext(
        cell={}, config={}, device_kind="cpu", moved=moved, compiles=[],
        trace=None, window_s=1.0,
    )


def test_file_agrees_with_its_entry():
    spec = files.metric(NAME)
    listed, = [
        m for m in files.benchmark_json()["per_layer"] if m["name"] == NAME
    ]
    for key in ("name", "unit", "better", "source", "layer", "moves"):
        assert spec[key] == listed[key], key
    assert (listed["unit"], listed["better"]) == ("ratio", "lower")
    assert listed["layer"] == "RMW pipeline"
    assert listed["workloads"] == LISTED_CELLS
    assert spec["reader"] == "counter_ratio"
    assert spec["numerator"] == [
        "osd.*.rmw:encode_ops", "osd.*.rmw:truncate_ops"
    ]
    assert spec["denominator"] == ["osd.*.rmw:encode_ops"]
    for cell in LISTED_CELLS:
        assert listed in files.metrics_for(cell, "per_layer")
    assert listed not in files.metrics_for(
        "rs84-4m.degraded-read", "per_layer"
    )


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


@pytest.mark.usefixtures(queued_cells_listed.__name__)
@pytest.mark.parametrize("cell", WRITE_CELLS)
def test_a_write_cell_reads_one_fanout_a_write(cell):
    code, last, text, _took = run_cell(
        cell, trace=1, devices=files.cell(cell)["chips"]
    )
    assert code == 0 and last["correct"], text
    assert rehearsal_readings(text)[NAME] == 1.0
