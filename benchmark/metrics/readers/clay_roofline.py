"""The Clay repair program's share of its roofline: the least time the
chip could take to move the bytes the window's repairs needed
(``trace/clay_cost.py``: helper bytes in once, rebuilt bytes out once,
at the published HBM peak) over the device time the trace gives the
program's runs. The cell names the program under ``clay_program``:
``{"match": <part of the module's name>, "line": "modules"}``, one
event per run of the whole program, so every kernel and every copy
between them is inside the time. A program without the repair counters
(a parent commit), or a trace without such an event, reads nothing."""

from __future__ import annotations

from ... import counters
from ...trace import clay_cost, kernel_cost, peaks, xplane


def read(spec: dict, ctx) -> float | None:
    program = ctx.cell.get("clay_program")
    if ctx.trace is None or not program:
        return None
    events = xplane.kernel_events(
        ctx.trace, program["match"], program.get("line", "modules")
    )
    helper = counters.total(ctx.moved, ["osd.*.read:repair_helper_bytes"])
    rebuilt = counters.total(ctx.moved, ["osd.*.read:repair_rebuilt_bytes"])
    spent = sum(seconds for _name, seconds in events)
    if not events or helper <= 0 or rebuilt <= 0 or spent <= 0:
        return None
    pool = ctx.config["pool"]
    want = clay_cost.helper_ratio(pool["k"], pool["m"], pool["d"])
    if abs(helper / rebuilt - want) > 0.01 * want:
        ctx.notes.append(
            f"{spec['name']}: {helper / rebuilt:.3f} helper bytes per "
            f"rebuilt byte, the code's design is {want:.3f}"
        )
    least, by = kernel_cost.least_seconds(
        clay_cost.repair_cost(int(helper), int(rebuilt)),
        peaks.published_peaks(ctx.device_kind),
    )
    ctx.notes.append(
        f"{spec['name']}: {len(events)} runs of {program['match']!r}, "
        f"{spent:.6f} s on the device, least {least:.6f} s, bound by {by}"
    )
    return 100.0 * least / spent
