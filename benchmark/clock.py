"""The run's clock: every wait of the harness has a deadline of its
own, and a whole-run alarm covers what the deadlines do not.

No run may outlast its clock. A wait that misses its deadline raises
``DeadlineMissed`` naming the wait and the state it was waiting on;
the alarm, a thread started before JAX is imported, prints the result
line with ``correct: false`` and ends the process."""

from __future__ import annotations

import os
import sys
import threading
import time


class DeadlineMissed(RuntimeError):
    """A harness wait ran past its deadline."""

    def __init__(self, wait: str, seconds: float, state: str) -> None:
        super().__init__(
            f"deadline missed: {wait} was not done in {seconds:g} s; {state}"
        )
        self.wait = wait


def wait_until(
    wait: str, seconds: float, done, state=lambda: "", poll: float = 0.05
) -> float:
    """Poll ``done()`` until true or ``seconds`` have passed; returns
    the seconds taken. ``state()`` describes what was seen, for the
    line printed when the deadline is missed."""
    t0 = time.monotonic()
    deadline = t0 + seconds
    while True:
        if done():
            return time.monotonic() - t0
        if time.monotonic() >= deadline:
            raise DeadlineMissed(wait, seconds, state())
        time.sleep(poll)


def call_with_deadline(wait: str, seconds: float, fn, state=lambda: ""):
    """Run ``fn()`` on a thread of its own and give up on it after
    ``seconds``: for calls into the program that take no timeout (boot,
    kill). The thread is a daemon; a run that gives up exits."""
    box: dict = {}

    def target() -> None:
        try:
            box["value"] = fn()
        except BaseException as e:  # re-raised on the caller's thread
            box["error"] = e

    t = threading.Thread(target=target, daemon=True, name=f"bench-{wait}")
    t.start()
    t.join(seconds)
    if t.is_alive():
        raise DeadlineMissed(wait, seconds, state())
    if "error" in box:
        raise box["error"]
    return box.get("value")


class Alarm:
    """Whole-run alarm. ``arm(seconds, line)`` may be called again to
    set the final value once the cell's file has been read; when it
    fires it writes ``line()`` as the last line of stdout and calls
    ``os._exit``."""

    EXIT_CODE = 3

    def __init__(self, out=None, exit_fn=os._exit) -> None:
        self._out = out if out is not None else sys.stdout
        self._exit = exit_fn
        self._cv = threading.Condition()
        self._at: float | None = None
        self._line = lambda: "{}"
        self._thread = threading.Thread(
            target=self._run, daemon=True, name="bench-alarm"
        )
        self._started = False

    def arm(self, seconds: float, line) -> None:
        with self._cv:
            self._at = time.monotonic() + seconds
            self._line = line
            if not self._started:
                self._started = True
                self._thread.start()
            self._cv.notify_all()

    def disarm(self) -> None:
        with self._cv:
            self._at = None
            self._cv.notify_all()

    def _run(self) -> None:
        with self._cv:
            while True:
                if self._at is None:
                    self._cv.wait()
                    continue
                left = self._at - time.monotonic()
                if left > 0:
                    self._cv.wait(left)
                    continue
                line = self._line
                break
        try:
            print("alarm: the whole-run alarm fired", file=sys.stderr)
            self._out.write("\n" + line() + "\n")
            self._out.flush()
        finally:
            self._exit(self.EXIT_CODE)
