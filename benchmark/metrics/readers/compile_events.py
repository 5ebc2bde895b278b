"""XLA backend compilations that ended inside the window
(``jax.monitoring``): there should be none."""

from __future__ import annotations


def read(spec: dict, ctx) -> float | None:
    if ctx.compiles:
        names: dict[str, int] = {}
        for fun, _secs, _at in ctx.compiles:
            names[fun] = names.get(fun, 0) + 1
        ctx.notes.append(f"compiled in the window: {names}")
    return float(len(ctx.compiles))
