"""The ten metrics of PR 38: the interpreter lock's wait as the native
frame calls keep it (``*.net:lock_waits`` / ``lock_waits_slow`` /
``call_seconds`` / ``lock_wait_seconds``) and the process's CPU by
thread role (``process.threads``), each a data file for
``counter_ratio_of``: every denominator is a counter the parent has, so
a program without the new counters reads nothing, not 0."""


import pytest

from benchmark import files, metrics

from .helpers import rehearsal_readings, run_cell

NET, THREADS = "*.net:", "process.threads:"
WALL = [NET + "send_seconds", NET + "recv_seconds"]
CPU = ["process:cpu_seconds"]
LOCK, MSGR, HOST = (
    "host process: interpreter lock", "messenger", "host process",
)

#: name -> (layer, unit, better, numerator, denominator, scale)
METRICS = {
    "lock_wait_us": (
        LOCK, "us", "lower", [NET + "lock_wait_seconds"],
        [NET + "lock_waits"], 1e6),
    "lock_wait_slow_pct": (
        LOCK, "%", "lower", [NET + "lock_waits_slow"],
        [NET + "lock_waits"], 100.0),
    "msgr_lock_pct": (
        MSGR, "%", "lower", [NET + "lock_wait_seconds"], WALL, 100.0),
    "msgr_call_pct": (
        MSGR, "%", "higher", [NET + "call_seconds"], WALL, 100.0),
    "msgr_handovers_per_op": (
        MSGR, "calls", "lower", [NET + "lock_waits"],
        ["loadgen_client:op_completed"], 1.0),
    "cpu_op_worker_pct": (
        HOST, "%", "lower", [THREADS + "op_worker_cpu_seconds"], CPU, 100.0),
    "cpu_msgr_pct": (
        HOST, "%", "lower", [THREADS + "msgr_cpu_seconds"], CPU, 100.0),
    "cpu_client_pct": (
        HOST, "%", "lower", [THREADS + "client_cpu_seconds"], CPU, 100.0),
    "cpu_tick_pct": (
        HOST, "%", "lower",
        [THREADS + "tick_cpu_seconds", THREADS + "unlisted_cpu_seconds"],
        CPU, 100.0),
    "cpu_runtime_pct": (
        HOST, "%", "lower", [THREADS + "runtime_cpu_seconds"], CPU, 100.0),
}


def context(moved: dict) -> metrics.RunContext:
    return metrics.RunContext(
        cell={}, config={}, device_kind="cpu", moved=moved, compiles=[],
        trace=None, window_s=1.0,
    )


def parents_delta() -> dict:
    """A window of the parent's program: every set the metrics stand
    over, none of the new keys."""
    moved = {
        "process:wall_seconds": 30.0, "process:cpu_seconds": 60.0,
        "loadgen_client:op_completed": 500.0,
        "loadgen_client.net:send_seconds": 10.0,
        "loadgen_client.net:recv_seconds": 2.0,
        "loadgen_client.net:io_calls": 1500.0,
    }
    for osd in range(12):
        moved[f"osd.{osd}.net:send_seconds"] = 8.0
        moved[f"osd.{osd}.net:recv_seconds"] = 15.0
        moved[f"osd.{osd}.net:io_calls"] = 2300.0
    return moved


def changes_delta() -> dict:
    moved = parents_delta()
    # 13 sets, 288 s of messenger wall, 29,100 calls
    for name in [f"osd.{osd}" for osd in range(12)] + ["loadgen_client"]:
        calls = moved[f"{name}.net:io_calls"]
        moved[f"{name}.net:lock_waits"] = calls
        moved[f"{name}.net:lock_waits_slow"] = calls / 4
        moved[f"{name}.net:lock_wait_seconds"] = calls * 0.008
        moved[f"{name}.net:call_seconds"] = calls * 0.0005
    moved.update({
        THREADS + "op_worker_cpu_seconds": 21.0,
        THREADS + "msgr_cpu_seconds": 18.0,
        THREADS + "tick_cpu_seconds": 3.0,
        THREADS + "ec_stream_cpu_seconds": 0.6,
        THREADS + "client_cpu_seconds": 1.2,
        THREADS + "other_python_cpu_seconds": 0.3,
        THREADS + "runtime_cpu_seconds": 3.3,
        THREADS + "unlisted_cpu_seconds": 12.6,
    })
    return moved


WANT = {
    "lock_wait_us": 8000.0,
    "lock_wait_slow_pct": 25.0,
    "msgr_lock_pct": 100 * 29100 * 0.008 / 288.0,
    "msgr_call_pct": 100 * 29100 * 0.0005 / 288.0,
    "msgr_handovers_per_op": 29100 / 500.0,
    "cpu_op_worker_pct": 35.0,
    "cpu_msgr_pct": 30.0,
    "cpu_client_pct": 2.0,
    "cpu_tick_pct": 26.0,
    "cpu_runtime_pct": 5.5,
}


@pytest.mark.parametrize("name", METRICS)
def test_file_agrees_with_its_entry_and_every_cell_reports_it(name):
    layer, unit, better, numerator, denominator, scale = METRICS[name]
    spec = files.metric(name)
    listed = next(
        m for m in files.benchmark_json()["per_layer"] if m["name"] == name
    )
    for key in ("name", "unit", "better", "source", "layer", "moves"):
        assert spec[key] == listed[key], key
    assert (listed["layer"], listed["unit"], listed["better"]) == (
        layer, unit, better
    )
    assert listed["source"] == "program_counter"
    assert listed["moves"] == "client_mbs"
    assert "workloads" not in listed
    assert spec["reader"] == "counter_ratio_of"
    assert spec["numerator"] == numerator
    assert spec["denominator"] == denominator
    assert spec["scale"] == scale
    for cell in files.benchmark_json()["workloads"]:
        assert listed in files.metrics_for(cell["name"], "per_layer")


def test_the_ten_are_appended_in_the_issues_order():
    assert [
        m["name"] for m in files.benchmark_json()["per_layer"][-10:]
    ] == list(METRICS)


@pytest.mark.parametrize("name", METRICS)
def test_the_parents_counters_read_nothing_not_zero(name):
    assert metrics.read(files.metric(name), context(parents_delta())) is None


@pytest.mark.parametrize("name", METRICS)
def test_it_reads_a_recorded_counter_delta(name):
    got = metrics.read(files.metric(name), context(changes_delta()))
    assert got == pytest.approx(WANT[name])


def test_a_link_on_the_python_path_reads_zero_waits_not_nothing():
    moved = changes_delta()
    for key in list(moved):
        if ".net:lock_" in key or key.endswith(".net:call_seconds"):
            moved[key] = 0.0
    ctx = context(moved)
    # no native call was made: no mean wait to give, and 0 % of the wall
    assert metrics.read(files.metric("lock_wait_us"), ctx) is None
    assert metrics.read(files.metric("msgr_lock_pct"), ctx) == 0.0
    assert metrics.read(files.metric("msgr_handovers_per_op"), ctx) == 0.0


def test_the_small_object_cell_reads_all_ten_on_a_rehearsal():
    cell = "rs84-64k.write"
    code, last, text, _took = run_cell(
        cell, trace=1, devices=files.cell(cell)["chips"]
    )
    assert code == 0 and last["correct"], text
    readings = rehearsal_readings(text)
    # shares of a CPU run are no measurement: they are there, and shares
    for name in METRICS:
        assert name in readings, name
    assert readings["lock_wait_us"] > 0.0
    assert 0.0 <= readings["lock_wait_slow_pct"] <= 100.0
    # call + lock lie inside the messenger's wall
    assert 0.0 < readings["msgr_lock_pct"] + readings["msgr_call_pct"] < 100.0
    # a 64 KiB write: 1 + 1 client frames, 12 sub-writes and replies,
    # each end once
    assert 20.0 < readings["msgr_handovers_per_op"] < 80.0
    roles = [n for n in METRICS if n.startswith("cpu_")]
    assert all(readings[n] >= 0.0 for n in roles)
    # ec_stream and other_python have no metric: the five stay under 100
    assert 50.0 < sum(readings[n] for n in roles) <= 100.5
