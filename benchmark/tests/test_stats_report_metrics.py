"""``stats_report_pct`` and ``census_keys_per_report`` (PR 36): what the
OSDs' PG-stats reports hold of one core's wall, and how many store keys
a report re-reads, each as a data file for a reader the benchmark had.
The twelve OSDs cut some twenty reports a second; since PR 36 a report
re-reads the keys that transactions touched since the last one, where
it used to walk the whole store. ``stats_report_pct`` stands over
``process:wall_seconds``, which every program has, so it is read by
``counter_ratio_of``: a program without ``osd.N.stats`` reads nothing,
not 0."""


import pytest

from benchmark import files, metrics

from .helpers import rehearsal_readings, run_cell

PCT, KEYS = "stats_report_pct", "census_keys_per_report"
LAYER = "OSD tick: stats plane"


def context(moved: dict) -> metrics.RunContext:
    return metrics.RunContext(
        cell={}, config={}, device_kind="cpu", moved=moved, compiles=[],
        trace=None, window_s=1.0,
    )


@pytest.mark.parametrize("name,unit,reader,numerator,denominator", [
    (PCT, "%", "counter_ratio_of", ["osd.*.stats:report_seconds"],
     ["process:wall_seconds"]),
    (KEYS, "keys", "counter_ratio", ["osd.*.stats:census_keys"],
     ["osd.*.stats:reports"]),
])
def test_file_agrees_with_its_entry_and_every_cell_reports_it(
    name, unit, reader, numerator, denominator,
):
    spec = files.metric(name)
    listed = next(
        m for m in files.benchmark_json()["per_layer"] if m["name"] == name
    )
    for key in ("name", "unit", "better", "source", "layer", "moves"):
        assert spec[key] == listed[key], key
    assert (listed["unit"], listed["better"]) == (unit, "lower")
    assert listed["layer"] == LAYER
    assert listed["source"] == "program_counter"
    assert listed["moves"] == "client_mbs"
    assert "workloads" not in listed
    assert spec["reader"] == reader
    assert spec["numerator"] == numerator
    assert spec["denominator"] == denominator
    for cell in files.benchmark_json()["workloads"]:
        assert listed in files.metrics_for(cell["name"], "per_layer")


def test_the_two_are_the_newest_entries():
    assert [m["name"] for m in files.benchmark_json()["per_layer"][-2:]] == [
        PCT, KEYS,
    ]


# twelve OSDs over a 30 s window: 600 reports
@pytest.mark.parametrize("seconds,keys,want_pct,want_keys", [
    (0.24, 16800, 0.8, 28.0),    # the kept census in the small-object cell
    (9.0, 600000, 30.0, 1000.0),  # a walk of 1,000 keys a report
    (0.21, 0, 0.7, 0.0),          # a store that stands still
])
def test_they_read_a_recorded_counter_delta(
    seconds, keys, want_pct, want_keys,
):
    moved = {"process:wall_seconds": 30.0, "process:cpu_seconds": 57.0}
    for osd in range(12):
        moved[f"osd.{osd}.stats:reports"] = 50.0
        moved[f"osd.{osd}.stats:report_seconds"] = seconds / 12
        moved[f"osd.{osd}.stats:report_cpu_seconds"] = seconds / 24
        moved[f"osd.{osd}.stats:census_keys"] = keys / 12
        moved[f"osd.{osd}.stats:census_walks"] = 0.0
        moved[f"osd.{osd}.store:txns"] = 1400.0
    ctx = context(moved)
    assert metrics.read(files.metric(PCT), ctx) == pytest.approx(want_pct)
    assert metrics.read(files.metric(KEYS), ctx) == pytest.approx(want_keys)


def test_a_program_without_the_counters_reads_nothing_not_zero():
    # the parent's dump: the process set and the store's, no osd.N.stats
    moved = {
        "process:wall_seconds": 30.0, "process:cpu_seconds": 57.0,
        "osd.3.store:txns": 1400.0, "osd.3.opq:ops": 120.0,
    }
    assert metrics.read(files.metric(PCT), context(moved)) is None
    assert metrics.read(files.metric(KEYS), context(moved)) is None
    # the set is there and no report was cut in the window
    moved.update({
        "osd.3.stats:reports": 0.0, "osd.3.stats:report_seconds": 0.0,
        "osd.3.stats:census_keys": 0.0,
    })
    assert metrics.read(files.metric(PCT), context(moved)) == 0.0
    assert metrics.read(files.metric(KEYS), context(moved)) is None


def test_the_small_object_cell_reads_both_on_a_rehearsal():
    cell = "rs84-64k.write"
    code, last, text, _took = run_cell(
        cell, trace=1, devices=files.cell(cell)["chips"]
    )
    assert code == 0 and last["correct"], text
    readings = rehearsal_readings(text)
    # a share of a CPU's wall on the sandbox is no measurement: it is
    # there and it is a share
    assert 0.0 < readings[PCT] < 100.0
    # every write makes one key on each of the twelve OSDs; a report
    # that walked would re-read the store (hundreds of keys by the
    # window's end, after a warm-up of 100 ops and more)
    assert 0.0 < readings[KEYS] < 100.0
