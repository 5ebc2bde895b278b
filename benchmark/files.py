"""Everything that belongs to one configuration, traffic mix, cell or
per-layer metric is a file of its own, found by the name that
``BENCHMARK.json`` gives it. A later PR adds files and entries and
edits none that is there."""

from __future__ import annotations

import functools
import importlib.util
import inspect
import json
import os
import types

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
#: where a pool's plain reference lives, one file a code
REFERENCE_DIR = os.path.join(HERE, "reference")
REFERENCE_FUNCTIONS = (
    "shards_of", "decode_data", "object_from_data_shards",
)


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


@functools.lru_cache(maxsize=None)
def _reference_module(path: str) -> types.ModuleType:
    """The reference at ``path``, run once a process."""
    if not os.path.isfile(path):
        raise FileNotFoundError(f"there is no file {path}")
    spec = importlib.util.spec_from_file_location(
        f"{__package__}.reference.{os.path.basename(path)[:-3]}", path
    )
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    missing = [
        f for f in REFERENCE_FUNCTIONS
        if not callable(getattr(module, f, None))
    ]
    if missing:
        raise AttributeError(
            f"reference {path} lacks {', '.join(missing)} (a reference "
            f"has {', '.join(REFERENCE_FUNCTIONS)})"
        )
    return module


def reference(config: dict) -> types.SimpleNamespace:
    """The plain reference of the configuration's pool:
    ``reference/<name>.py``, ``<name>`` being ``pool.reference``
    (``rs_vandermonde`` where the file has none), with its three
    functions. One that takes a ``pool`` keyword gets the pool's dict
    (a code that needs more than k and m: ``d``, a layout)."""
    pool = config["pool"]
    name = pool.get("reference", "rs_vandermonde")
    try:
        module = _reference_module(os.path.join(REFERENCE_DIR, name + ".py"))
    except FileNotFoundError as e:
        raise FileNotFoundError(
            f"configuration {config.get('name')!r}: pool.reference is "
            f"{name!r} and {e}"
        ) from None
    functions = {}
    for f in REFERENCE_FUNCTIONS:
        fn = getattr(module, f)
        if "pool" in inspect.signature(fn).parameters:
            fn = functools.partial(fn, pool=pool)
        functions[f] = fn
    return types.SimpleNamespace(name=name, **functions)


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
