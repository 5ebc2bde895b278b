"""Backend selection helpers: which device JAX found, the gate the
measuring entry points put in front of everything else, the one
persistent compile-cache location, and the debug-flag plumbing.

``JAX_PLATFORMS`` selects the backend by itself (``cpu`` for the unit
tests and the driver's virtual-mesh dry run; unset on a TPU host).
Nothing here overrides it.
"""

from __future__ import annotations

import os

#: checkout root (the directory holding ``ceph_tpu/``): the persistent
#: compile cache lives under it so its path — part of the cache key —
#: is the same for every entry point of one checkout
_CHECKOUT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)


def on_tpu() -> bool:
    """True when the default JAX backend is a TPU. The ONE predicate
    every kernel route consults. A backend that fails to initialise
    raises here — a chip that was expected and not found must stop the
    program, not reroute it to the interpreter."""
    import jax

    return jax.devices()[0].platform == "tpu"


def pallas_interpret() -> bool:
    """What ``interpret=None`` resolves to in every kernel wrapper.
    Mosaic compiles for the TPU backend only; on any other backend the
    kernel body runs in the Pallas interpreter — the CPU tests'
    convenience. Deliberately NOT ``not on_tpu()``: tests patch
    ``on_tpu`` to walk the TPU routes on a CPU backend, and the
    kernels those routes reach must still be executable there."""
    import jax

    return jax.devices()[0].platform != "tpu"


def device_identity() -> dict:
    """``{"platform", "kind", "count"}`` as JAX reports the default
    backend — the identity every printed result carries."""
    import jax

    devs = jax.devices()
    return {
        "platform": devs[0].platform,
        "kind": devs[0].device_kind,
        "count": len(devs),
    }


def require_tpu() -> dict:
    """The gate for entry points that measure (``chip_smoke.py``,
    ``benchmark/run.py``): return the device identity, or raise naming what
    JAX found instead. Off-TPU the kernel wrappers' ``interpret=None``
    convenience would run every Pallas kernel in the interpreter and
    report its times under device names — so those programs stop
    here."""
    device = device_identity()
    if device["platform"] != "tpu":
        raise RuntimeError(
            f"a TPU is required but JAX found platform="
            f"{device['platform']!r} device_kind={device['kind']!r} "
            f"count={device['count']} (JAX_PLATFORMS="
            f"{os.environ.get('JAX_PLATFORMS')!r})"
        )
    return device


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its
    directory. Called by every entry point before first device use.

    ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself; no
    directory is set in code. Unset: ``<checkout>/.jax_cache`` — a
    fixed path, because the path is part of the cache key and a
    directory that moves never hits. Only compilations slower than
    ``jax_persistent_cache_min_compile_time_secs`` (1.0 s by default)
    are written."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    path = os.path.join(_CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def trace_state_clean() -> bool:
    """True when no jax trace is active — the guard for caching device
    arrays (a tracer cached from inside jit poisons every later
    call)."""
    from jax._src import core

    return bool(core.trace_state_clean())


def apply_debug_modes() -> None:
    """Map the debug_* config options onto JAX debug flags — the
    runtime analog of the reference's WITH_ASAN/WITH_TSAN compile-time
    sanitizer toggles (CMakeLists.txt:673-690; SURVEY.md §5.2). Safe
    to call any time; also installed as a config observer so
    ``config set debug_nan_check true`` takes effect live."""
    import jax

    from ceph_tpu.utils.config import config

    jax.config.update("jax_debug_nans", config.get("debug_nan_check"))
    jax.config.update("jax_disable_jit", config.get("debug_disable_jit"))


def install_debug_observer() -> None:
    """Re-apply debug modes whenever a debug_* option changes."""
    from ceph_tpu.utils.config import config

    config.add_observer("debug_", lambda _name, _value: apply_debug_modes())
