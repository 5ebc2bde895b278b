"""No run outlasts its clock: a missed deadline and the whole-run alarm
each end a stalled run with ``correct: false`` inside their limit."""

import io
import json
import threading
import time

import pytest

from benchmark import clock

from .helpers import run_cell

#: a cluster whose writes never complete, and deadlines cut to seconds
STALL = '''
import benchmark.files as F
_cell = F.cell
def cell(name):
    spec = _cell(name)
    spec["deadlines_s"] = {k: 3 for k in spec["deadlines_s"]}
    return spec
F.cell = cell
R.STARTUP_ALLOWANCE_S = 20.0
class _Io:
    def aio_write_full(self, *a, **k): pass
    aio_write = aio_read = aio_write_full
class _Cluster:
    io = _Io(); dead = []
    def shutdown(self): pass
R.boot = lambda cell, config: _Cluster()
R.hold_scrubs = lambda cluster: None
'''


def test_wait_until_names_the_wait_and_the_state():
    with pytest.raises(clock.DeadlineMissed) as e:
        clock.wait_until("peering", 0.2, lambda: False, lambda: "pg 3 stuck")
    assert e.value.wait == "peering" and "pg 3 stuck" in str(e.value)
    assert clock.wait_until("ok", 1.0, lambda: True) < 0.5


def test_call_with_deadline_gives_up_on_a_call_that_hangs():
    hang = threading.Event()
    t0 = time.monotonic()
    with pytest.raises(clock.DeadlineMissed):
        clock.call_with_deadline("boot", 0.3, hang.wait)
    assert time.monotonic() - t0 < 2.0
    hang.set()
    assert clock.call_with_deadline("sum", 1.0, lambda: 1 + 1) == 2
    with pytest.raises(ZeroDivisionError):
        clock.call_with_deadline("div", 1.0, lambda: 1 / 0)


def test_alarm_prints_the_line_last_and_exits():
    out, codes = io.StringIO(), []
    fired = threading.Event()

    def exit_fn(code):
        codes.append(code)
        fired.set()

    alarm = clock.Alarm(out=out, exit_fn=exit_fn)
    alarm.arm(30.0, lambda: "never")
    alarm.arm(0.2, lambda: json.dumps({"correct": False}))  # re-armed
    assert fired.wait(3.0)
    assert codes == [clock.Alarm.EXIT_CODE]
    assert json.loads(out.getvalue().strip().splitlines()[-1]) == {
        "correct": False
    }


def test_a_disarmed_alarm_stays_silent():
    codes = []
    alarm = clock.Alarm(out=io.StringIO(), exit_fn=codes.append)
    alarm.arm(0.2, lambda: "x")
    alarm.disarm()
    time.sleep(0.5)
    assert codes == []


def test_a_stalled_cluster_misses_its_deadline_with_correct_false():
    code, last, out, took = run_cell("rs84-4m.write", prelude=STALL)
    assert code != 0, out
    assert last is not None and last["correct"] is False, out
    assert "deadline missed: warmup" in out
    assert took < 30, took


def test_the_whole_run_alarm_ends_a_run_that_no_deadline_covers():
    prelude = STALL + '''
import time
R.warm_up = lambda gen, log, cell: time.sleep(600)
'''
    # deadlines 7 x 3 s + 4 s window + 20 s allowance = 45 s
    code, last, out, took = run_cell("rs84-4m.write", prelude=prelude)
    assert code == clock.Alarm.EXIT_CODE, out
    assert last is not None and last["correct"] is False, out
    assert set(last) >= {"correct", "attempted", "failed", "metrics", "device"}
    assert 30 < took < 60, took
