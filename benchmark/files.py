"""Everything that belongs to one configuration, traffic mix, cell or
per-layer metric is a file of its own, found by the name that
``BENCHMARK.json`` gives it. A later PR adds files and entries and
edits none that is there."""

from __future__ import annotations

import functools
import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def _load(path: str) -> dict:
    with open(path, encoding="utf-8") as f:
        return json.load(f)


@functools.lru_cache(maxsize=1)
def benchmark_json() -> dict:
    """``BENCHMARK.json``, read once; callers do not change it."""
    return _load(os.path.join(ROOT, "BENCHMARK.json"))


def cell(name: str) -> dict:
    """``benchmark/workloads/<cell>.json`` with its ``BENCHMARK.json``
    entry: the cell has to be listed there."""
    entries = {w["name"]: w for w in benchmark_json()["workloads"]}
    if name not in entries:
        raise KeyError(
            f"no workload {name!r} in BENCHMARK.json (know {sorted(entries)})"
        )
    spec = _load(os.path.join(HERE, "workloads", name + ".json"))
    entry = entries[name]
    for key in ("config", "traffic", "chips"):
        if spec[key] != entry[key]:
            raise ValueError(
                f"{name}: {key} is {spec[key]!r} in its file and "
                f"{entry[key]!r} in BENCHMARK.json"
            )
    return spec


def config(name: str) -> dict:
    entries = {c["name"]: c for c in benchmark_json()["configs"]}
    return _load(os.path.join(ROOT, entries[name]["file"]))


def mix(name: str) -> dict:
    return _load(os.path.join(HERE, "traffic", "mixes", name + ".json"))


def metric(name: str) -> dict:
    return _load(os.path.join(HERE, "metrics", name + ".json"))


def metrics_for(cell_name: str, group: str) -> list[dict]:
    """The ``end_to_end`` or ``per_layer`` entries of ``BENCHMARK.json``
    that this cell reports: an entry without ``workloads`` is reported
    by every cell."""
    return [
        m for m in benchmark_json()[group]
        if cell_name in m.get("workloads", [cell_name])
    ]


def benchmark_spans() -> list[str]:
    """The program's span names, innermost first (``trace/spans.json``):
    what an idle gap of the device is attributed to."""
    return _load(os.path.join(HERE, "trace", "spans.json"))["innermost_first"]
