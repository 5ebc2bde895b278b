"""A codec kernel's share of its roofline: the least time the chip
could take for its calls, by the published peaks, over the device time
the trace gives them. Which kernel is the cell's dominant one, and
which cost function reads its shapes, is in the cell's own file under
``codec_kernel``: ``{"match": <part of the device event's name>,
"csum": <bool>}`` for a kernel whose event name carries its shapes, or,
for a whole program across chips (``"line": "modules"``), ``{"counter":
<the ec_dispatch byte counter of its route>, "rows": <rows out>}``: the
data bytes then come from the counter, every chip's runs are summed,
and each chip's least time is its share of the bytes at its own peak."""

from __future__ import annotations

from ... import counters
from ...trace import kernel_cost, peaks, xplane


def read(spec: dict, ctx) -> float | None:
    kernel = ctx.cell.get("codec_kernel")
    if ctx.trace is None or not kernel:
        return None
    events = xplane.kernel_events(
        ctx.trace, kernel["match"], kernel.get("line", "ops")
    )
    if not events:
        return None
    pool = ctx.config["pool"]
    table = peaks.published_peaks(ctx.device_kind)
    csum_block = pool["chunk_size"] if kernel.get("csum") else 0
    least = spent = 0.0
    bound: dict[str, int] = {}
    if "counter" in kernel:
        data_bytes = counters.total(ctx.moved, [kernel["counter"]])
        if data_bytes <= 0:
            return None
        cost = kernel_cost.bitmatrix_cost(
            int(data_bytes), pool["k"], kernel["rows"], csum_block
        )
        least, by = kernel_cost.least_seconds(cost, table)
        spent = sum(seconds for _name, seconds in events)
        bound[by] = calls = len(events)
    else:
        calls = 0
        for name, seconds in events:
            cost = kernel_cost.bitmatrix_apply(name, pool["k"], csum_block)
            if cost is None:
                continue
            best, by = kernel_cost.least_seconds(cost, table)
            least += best
            spent += seconds
            bound[by] = bound.get(by, 0) + 1
            calls += 1
    if not calls or spent <= 0:
        return None
    ctx.notes.append(
        f"{spec['name']}: {calls} calls of {kernel['match']!r}, "
        f"{spent:.6f} s on the device, least {least:.6f} s, bound by {bound}"
    )
    return 100.0 * least / spent
