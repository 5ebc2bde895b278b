"""Shared by the benchmark's own tests: run one cell in a process of
its own on the CPU, optionally with something broken underneath."""

from __future__ import annotations

import copy
import json
import os
import subprocess
import sys
import time

import pytest

from benchmark import files

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))

with open(os.path.join(HERE, "queued_cells.json"), encoding="utf-8") as _f:
    #: cells whose files are here and whose entries are not in
    #: ``BENCHMARK.json`` yet (PERF.md, Open questions, says why)
    QUEUED = json.load(_f)["cells"]


def with_queued_cells(bench: dict) -> dict:
    """``BENCHMARK.json`` as it will read once a later PR lists the
    queued cells: each cell's entry from its ``workloads/`` file, the
    cell added to the metrics that will list it, its own metrics
    appended."""
    bench = copy.deepcopy(bench)
    listed = {w["name"] for w in bench["workloads"]}
    for name, queued in QUEUED.items():
        if name in listed:  # a later PR listed it: nothing to add
            continue
        with open(
            os.path.join(files.HERE, "workloads", name + ".json"),
            encoding="utf-8",
        ) as f:
            spec = json.load(f)
        bench["workloads"].append(
            {k: spec[k] for k in ("name", "config", "traffic", "chips", "why")}
        )
        for m in bench["per_layer"]:
            if m["name"] in queued["per_layer_lists"]:
                m["workloads"].append(name)
        bench["per_layer"] += queued["per_layer_entries"]
    return bench


def enlist_queued_cells() -> None:
    """For this process the queued cells are listed: the harness finds
    them as it finds any cell."""
    enlarged = with_queued_cells(files.benchmark_json())
    files.benchmark_json = lambda: enlarged


@pytest.fixture
def queued_cells_listed(monkeypatch):
    """The same for one test of this process."""
    enlarged = with_queued_cells(files.benchmark_json())
    monkeypatch.setattr(files, "benchmark_json", lambda: enlarged)


#: in the child, before ``main``: a queued cell runs as a listed one
ENLIST_QUEUED = (
    "import benchmark.tests.helpers as _H\n_H.enlist_queued_cells()\n"
)


def run_cell(
    cell: str, *, seed: int = 3000000019, seconds: float = 4.0,
    trace: int = 0, prelude: str = "", devices: int = 1,
    timeout: float = 240.0, rehearse: bool = True,
) -> tuple[int, dict | None, str, float]:
    """(exit code, the last line as JSON or None, all output, seconds).
    ``prelude`` is Python run in the child before ``main``: the place
    to break the program or the harness."""
    argv = ["--workload", cell, "--seed", str(seed), "--seconds",
            str(seconds), "--trace", str(trace)]
    if rehearse:
        argv.append("--rehearse")
    code = (
        "import benchmark.run as R\n" + ENLIST_QUEUED + prelude
        + f"\nR.main({argv!r})\n"
    )
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    t0 = time.monotonic()
    proc = subprocess.run(
        [sys.executable, "-c", code], cwd=ROOT, env=env, timeout=timeout,
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
    )
    took = time.monotonic() - t0
    lines = [ln for ln in proc.stdout.splitlines() if ln.strip()]
    last = None
    if lines and lines[-1].startswith("{"):
        try:
            last = json.loads(lines[-1])
        except ValueError:
            last = None
    return proc.returncode, last, proc.stdout, took


#: in the child, before ``main``: print the window's counter names
PRINT_COUNTER_NAMES = '''
import json
import benchmark.counters as C
_delta = C.delta
def delta(before, after):
    moved = _delta(before, after)
    print("COUNTERS " + json.dumps(sorted(moved)), flush=True)
    return moved
C.delta = delta
'''


def counters_and_readings(text: str) -> tuple[list[str], dict]:
    """From a traced rehearsal run under ``PRINT_COUNTER_NAMES``: the
    counter names that moved in the window, and the readings."""
    lines = text.splitlines()
    names = json.loads(next(
        ln for ln in lines if ln.startswith("COUNTERS ")
    )[len("COUNTERS "):])
    return names, rehearsal_readings(text)


def rehearsal_readings(text: str) -> dict:
    return json.loads(next(
        ln for ln in text.splitlines() if "rehearsal readings" in ln
    ).split("): ", 1)[1])["metrics"]
