"""Per-layer metrics: one data file each (``<name>.json``: layer, unit,
source, what it moves, and the reader with its parameters), and one
small reader per kind under ``readers/``, found by the name the file
gives. A reader that finds nothing to read returns None and the harness
leaves the metric out of the line."""

from __future__ import annotations

import dataclasses
import importlib


@dataclasses.dataclass
class RunContext:
    """What one traced run hands to the readers."""

    cell: dict
    config: dict
    device_kind: str
    #: counter deltas over the window (``counters.delta``)
    moved: dict
    #: compilations that ended inside the window
    compiles: list
    #: the reduced profiler trace (``trace.xplane.Trace``) or None
    trace: object
    window_s: float
    #: the generator's samples of the ops issued in the window
    samples: list = dataclasses.field(default_factory=list)
    #: free-form findings a reader wants printed on an earlier line
    notes: list = dataclasses.field(default_factory=list)


def read(spec: dict, ctx: RunContext):
    reader = importlib.import_module(
        f"{__name__}.readers.{spec['reader']}"
    )
    return reader.read(spec, ctx)
