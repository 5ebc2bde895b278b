"""The per-layer metrics that read the program's stage timers (PR 24):
each is a data file for ``counter_ratio`` and nothing else. Every file
loads and agrees with its ``BENCHMARK.json`` entry, every counter it
names exists in a rehearsal of each cell that lists it, and the reader
gives a number there. A CPU rehearsal proves names and plumbing, never
a time."""

import fnmatch

import pytest

from benchmark import files

from .helpers import PRINT_COUNTER_NAMES, counters_and_readings, run_cell

STAGE_METRICS = [
    "opq_wait_ms", "osd_op_cpu_pct", "msgr_ms_per_mb", "wire_bytes_ratio",
    "ec_write_assemble_ms", "ec_write_encode_ms", "ec_write_txn_ms",
    "ec_write_fanout_ms", "subop_wait_ms", "read_gather_ms",
    "read_reconstruct_ms", "codec_host_ms", "store_txn_ms",
    "store_read_ms", "host_cpu_ms_per_mb", "host_cpu_cores",
    "read_finish_ms",
]
CELLS = [w["name"] for w in files.benchmark_json()["workloads"]]

def entry(name: str) -> dict:
    return next(
        m for m in files.benchmark_json()["per_layer"] if m["name"] == name
    )


def cells_of(name: str) -> list[str]:
    return entry(name).get("workloads", CELLS)


@pytest.fixture(scope="module")
def rehearsals():
    """cell -> (counter names over the traced window, readings)."""
    out = {}
    for cell in CELLS:
        chips = files.cell(cell)["chips"]
        code, last, text, _took = run_cell(
            cell, trace=1, devices=chips, prelude=PRINT_COUNTER_NAMES
        )
        assert code == 0 and last["correct"], text
        out[cell] = counters_and_readings(text)
    return out


@pytest.mark.parametrize("name", STAGE_METRICS)
def test_file_loads_and_agrees_with_its_entry(name):
    spec = files.metric(name)
    assert spec["reader"] == "counter_ratio"
    assert spec["numerator"] and spec["denominator"]
    listed = entry(name)
    for key in ("name", "unit", "better", "source", "layer", "moves"):
        assert spec[key] == listed[key], key
    assert listed["source"] == "program_counter"
    assert listed["moves"] == "client_mbs"
    assert set(cells_of(name)) <= set(CELLS)


def test_the_entries_are_listed_once_each():
    """Membership, not position: every later PR appends entries."""
    names = [m["name"] for m in files.benchmark_json()["per_layer"]]
    for name in STAGE_METRICS:
        assert names.count(name) == 1, name


def test_every_cell_that_writes_lists_the_write_stages():
    """Open question 15, closed by PR 32: a cell whose mix writes runs
    every write stage and reads its stores, so it reports them."""
    writes = {"write_new", "write_full", "write_patch", "append"}
    for cell in CELLS:
        mix = files.mix(files.cell(cell)["traffic"])
        if writes & {c["op"] for c in mix["classes"]}:
            for name in ("ec_write_assemble_ms", "ec_write_encode_ms",
                         "ec_write_txn_ms", "ec_write_fanout_ms",
                         "subop_wait_ms", "store_txn_ms"):
                assert cell in cells_of(name), (cell, name)


@pytest.mark.parametrize("name", STAGE_METRICS)
def test_every_counter_it_names_exists_where_it_is_listed(name, rehearsals):
    spec = files.metric(name)
    for cell in cells_of(name):
        names, _readings = rehearsals[cell]
        for pattern in spec["numerator"] + spec["denominator"]:
            assert any(fnmatch.fnmatchcase(n, pattern) for n in names), (
                f"{name} in {cell}: no counter matches {pattern!r}"
            )


@pytest.mark.parametrize("name", STAGE_METRICS)
def test_the_reader_gives_a_number_where_it_is_listed(name, rehearsals):
    for cell in CELLS:
        _names, readings = rehearsals[cell]
        if cell in cells_of(name):
            assert isinstance(readings.get(name), float), (cell, readings)
            assert readings[name] >= 0
        else:
            assert name not in readings


def test_a_program_without_the_counters_leaves_the_metrics_out():
    """What the parent commit gives: no such counter, so no reading,
    and no error."""
    from benchmark import metrics

    ctx = metrics.RunContext(
        cell={}, config={}, device_kind="cpu",
        # counters the parent has too, all of them moving
        moved={"loadgen_client:op_completed": 10.0,
               "ec_dispatch:fused_encode": 10.0,
               "osd.3.loadpool.1.rmw:write_ops": 10.0,
               "osd.3.loadpool.1.read:read_ops": 10.0,
               "osd.3.loadpool.1.read:reconstruct_ops": 7.0,
               "osd.3.net:frames_dropped": 1.0},
        compiles=[], trace=None, window_s=1.0,
    )
    for name in STAGE_METRICS:
        assert metrics.read(files.metric(name), ctx) is None
