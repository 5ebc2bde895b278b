"""Shared by the benchmark's own tests: run one cell in a process of
its own on the CPU, optionally with something broken underneath."""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


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
        "import benchmark.run as R\n" + prelude
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
