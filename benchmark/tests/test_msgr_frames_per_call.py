"""``msgr_frames_per_call`` (PR 31): frames the messenger moves for
every time it leaves the interpreter, as a data file for
``counter_ratio``. A frame is counted once where it is sent and once
where it is received; the native frame I/O sends one in one call and
receives one in one or two, so it reads 0.67 to 1.0, and the Python
path (a codec call and ``sendall``; three ``recv`` or more and a codec
call) 0.33 or less. ``io_calls`` is the denominator on purpose: a
program without the counter reads nothing, not 0."""


import pytest

from benchmark import files, metrics

from .helpers import rehearsal_readings, run_cell

NAME = "msgr_frames_per_call"


def context(moved: dict) -> metrics.RunContext:
    return metrics.RunContext(
        cell={}, config={}, device_kind="cpu", moved=moved, compiles=[],
        trace=None, window_s=1.0,
    )


def test_file_agrees_with_its_entry_and_every_cell_reports_it():
    spec = files.metric(NAME)
    listed = next(
        m for m in files.benchmark_json()["per_layer"] if m["name"] == NAME
    )
    for key in ("name", "unit", "better", "source", "layer", "moves"):
        assert spec[key] == listed[key], key
    assert (listed["unit"], listed["better"]) == ("frames", "higher")
    assert listed["layer"] == "messenger"
    assert listed["moves"] == "client_mbs"
    assert "workloads" not in listed
    assert spec["reader"] == "counter_ratio"
    assert spec["numerator"] == ["*.net:frames_sent", "*.net:frames_recv"]
    assert spec["denominator"] == ["*.net:io_calls"]
    for cell in files.benchmark_json()["workloads"]:
        assert listed in files.metrics_for(cell["name"], "per_layer")


# one 4 MiB write: 24 frames, each counted at both ends
@pytest.mark.parametrize("calls,want", [
    (24 * 1 + 12 * 2 + 12 * 1, 0.8),   # native: a send 1, a receive 2 or 1
    (24 * 2 + 24 * 4, 1 / 3),          # the Python path at its best
    (48, 1.0),                         # the most a frame can do
])
def test_it_reads_a_recorded_counter_delta(calls, want):
    moved = {
        "osd.3.net:frames_sent": 12.0, "osd.3.net:frames_recv": 12.0,
        "osd.4.net:frames_sent": 11.0, "osd.4.net:frames_recv": 11.0,
        "loadgen_client.net:frames_sent": 1.0,
        "loadgen_client.net:frames_recv": 1.0,
        "osd.3.net:io_calls": float(calls - 2),
        "loadgen_client.net:io_calls": 2.0,
        "osd.3.net:bytes_sent": 9.5e6,
    }
    assert metrics.read(files.metric(NAME), context(moved)) == (
        pytest.approx(want)
    )


def test_a_program_without_the_counter_reads_nothing_not_zero():
    # the parent's dump: frames, bytes and seconds, no io_calls
    moved = {
        "osd.3.net:frames_sent": 12.0, "osd.3.net:frames_recv": 12.0,
        "osd.3.net:bytes_sent": 9.5e6, "osd.3.net:send_seconds": 0.4,
        "osd.3.net:recv_seconds": 0.3,
    }
    assert metrics.read(files.metric(NAME), context(moved)) is None
    moved["osd.3.net:io_calls"] = 0.0
    assert metrics.read(files.metric(NAME), context(moved)) is None


@pytest.mark.parametrize("cell", ["rs84-4m.write", "rs84-64k.write"])
def test_a_cell_reads_the_native_share(cell):
    code, last, text, _took = run_cell(
        cell, trace=1, devices=files.cell(cell)["chips"]
    )
    assert code == 0 and last["correct"], text
    assert 0.67 <= rehearsal_readings(text)[NAME] <= 1.0
