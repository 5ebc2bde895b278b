"""Share of the traced window in which no operation ran on the device:
1 - union of device-op intervals over the window, the mean over the
chips the cell uses."""

from __future__ import annotations

from ...trace import xplane


def read(spec: dict, ctx) -> float | None:
    if ctx.trace is None or not ctx.trace.devices or ctx.window_s <= 0:
        return None
    busy = xplane.busy_seconds(ctx.trace)
    mean_busy = sum(busy.values()) / len(busy)
    return 100.0 * (1.0 - mean_busy / ctx.window_s)
