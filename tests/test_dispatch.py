"""Kernel-path dispatch: decode/delta ride the Pallas kernel when it
applies, the einsum engine otherwise, host GF tables for small numpy
inputs — and every route is visible in the ``ec_dispatch`` perf
counters (VERDICT r1: silent fallback must not exist).
"""

import functools

import numpy as np
import pytest

from ceph_tpu.codecs.matrix_codec import _dispatch_counters
from ceph_tpu.codecs.registry import registry
from ceph_tpu.ops import pallas_encode as pe
from ceph_tpu.ops.pallas_encode import LANE_TILE
from ceph_tpu.utils import platform


def _snap():
    pc = _dispatch_counters()
    return {k: pc.get(k) for k in pc.dump()}


def _delta(before, after):
    return {k: after[k] - before[k] for k in after if after[k] != before[k]}


@pytest.fixture
def isa_codec():
    codec = registry.factory("isa", {"k": "4", "m": "2"})
    return codec


def _device_chunks(rng, codec, n):
    import jax.numpy as jnp

    data = {
        i: jnp.asarray(rng.integers(0, 256, (n,), np.uint8))
        for i in range(codec.k)
    }
    return data


def test_einsum_paths_counted(rng, isa_codec):
    before = _snap()
    data = _device_chunks(rng, isa_codec, 4096)
    parity = isa_codec.encode_chunks(data)
    chunks = dict(data) | parity
    del chunks[0], chunks[5]
    out = isa_codec.decode_chunks({0, 5}, chunks)
    d = _delta(before, _snap())
    assert d.get("einsum_encode", 0) >= 1
    assert d.get("einsum_decode", 0) >= 1
    np.testing.assert_array_equal(np.asarray(out[0]), np.asarray(data[0]))


def test_host_paths_counted(rng, isa_codec):
    before = _snap()
    data = {i: rng.integers(0, 256, (512,), np.uint8) for i in range(4)}
    parity = isa_codec.encode_chunks(data)
    assert all(isinstance(p, np.ndarray) for p in parity.values())
    chunks = dict(data) | parity
    del chunks[1]
    isa_codec.decode_chunks({1}, chunks)
    d = _delta(before, _snap())
    assert d.get("host_encode", 0) >= 1
    assert d.get("host_decode", 0) >= 1


def test_pallas_fallback_counted(rng, isa_codec, monkeypatch):
    """Pallas enabled + on TPU + untileable shape -> fallback counter
    ticks and the einsum engine serves the op (no silent drop)."""
    monkeypatch.setattr(platform, "on_tpu", lambda: True)
    before = _snap()
    data = _device_chunks(rng, isa_codec, LANE_TILE + 256)
    isa_codec.encode_chunks(data)
    d = _delta(before, _snap())
    assert d.get("pallas_fallback", 0) >= 1
    assert d.get("einsum_encode", 0) >= 1


def test_pallas_decode_path(rng, isa_codec, monkeypatch):
    """With the TPU predicate forced on (kernel in interpreter mode so
    CPU CI runs it), decode routes through the Pallas kernel and is
    bit-exact vs the original data."""
    monkeypatch.setattr(platform, "on_tpu", lambda: True)
    monkeypatch.setattr(
        pe,
        "gf_encode_bitplane_pallas",
        functools.partial(pe.gf_encode_bitplane_pallas, interpret=True),
    )
    before = _snap()
    data = _device_chunks(rng, isa_codec, LANE_TILE)
    parity = isa_codec.encode_chunks(data)
    chunks = dict(data) | parity
    del chunks[2], chunks[4]
    out = isa_codec.decode_chunks({2, 4}, chunks)
    d = _delta(before, _snap())
    assert d.get("pallas_encode", 0) >= 1
    assert d.get("pallas_decode", 0) >= 1
    np.testing.assert_array_equal(np.asarray(out[2]), np.asarray(data[2]))


def test_pallas_delta_path(rng, isa_codec, monkeypatch):
    import jax.numpy as jnp

    monkeypatch.setattr(platform, "on_tpu", lambda: True)
    monkeypatch.setattr(
        pe,
        "gf_encode_bitplane_pallas",
        functools.partial(pe.gf_encode_bitplane_pallas, interpret=True),
    )
    data = _device_chunks(rng, isa_codec, LANE_TILE)
    parity = isa_codec.encode_chunks(data)
    new1 = jnp.asarray(
        rng.integers(0, 256, (LANE_TILE,), np.uint8)
    )
    before = _snap()
    # column 1, not 0: the ISA generator's column 0 is all ones, and a
    # 0/1 delta column rides the XOR-schedule route on a TPU
    delta = {1: isa_codec.encode_delta(data[1], new1)}
    updated = isa_codec.apply_delta(delta, parity)
    d = _delta(before, _snap())
    assert d.get("pallas_delta", 0) >= 1
    # parity after delta == parity of the updated data
    data2 = dict(data) | {1: new1}
    fresh = isa_codec.encode_chunks(data2)
    for pid in parity:
        np.testing.assert_array_equal(
            np.asarray(updated[pid]), np.asarray(fresh[pid])
        )


class TestBitMatrixFamilyOnEngine:
    """liberation-family dispatch rides the same engine as the byte
    codes (VERDICT r3 weak #3): every route counted, host shortcut
    for small numpy inputs, einsum on CPU CI."""

    @pytest.fixture
    def lib_codec(self):
        return registry.factory(
            "jerasure",
            {"technique": "liberation", "k": "4", "m": "2", "w": "7"},
        )

    def test_device_routes_counted(self, rng, lib_codec):
        import jax.numpy as jnp

        before = _snap()
        n = 7 * 2048  # w packets of a lane-tileable size
        data = {
            i: jnp.asarray(rng.integers(0, 256, (n,), np.uint8))
            for i in range(4)
        }
        parity = lib_codec.encode_chunks(data)
        chunks = dict(data) | parity
        # two DATA erasures: the decode matrix needs the inverted
        # X-block compositions — dense in RAW form (~50% ones), but
        # round 11's CSE compresses it under the op-count gate, so it
        # now rides the schedule route too (the superopt headline;
        # tests/test_sched_superopt.py pins the gate math)
        del chunks[0], chunks[1]
        out = lib_codec.decode_chunks({0, 1}, chunks)
        deltas = {
            i: jnp.asarray(rng.integers(0, 256, (n,), np.uint8))
            for i in (1, 2)
        }
        lib_codec.apply_delta(deltas, {4: parity[4], 5: parity[5]})
        d = _delta(before, _snap())
        # encode: the sparse coding matrix rides the XOR-schedule route
        assert d.get("sched_encode", 0) >= 1
        assert d.get("einsum_encode", 0) == 0
        assert d.get("sched_decode", 0) >= 1
        assert d.get("einsum_decode", 0) == 0
        assert d.get("sched_delta", 0) >= 1
        np.testing.assert_array_equal(
            np.asarray(out[0]), np.asarray(data[0])
        )
        # the un-optimized selection form (escape hatch) keeps the
        # pre-round-11 routing: raw density rejects the inverted
        # matrix and the generic engine serves it, rejection counted
        from ceph_tpu.utils import config

        before = _snap()
        with config.override(ec_sched_opt=False):
            out2 = lib_codec.decode_chunks({0, 1}, dict(chunks))
        d = _delta(before, _snap())
        assert d.get("einsum_decode", 0) >= 1
        assert d.get("sched_decode", 0) == 0
        assert d.get("sched_rejected_density", 0) >= 1
        np.testing.assert_array_equal(
            np.asarray(out2[0]), np.asarray(out[0])
        )

    def test_sched_route_matches_engine(self, rng, lib_codec):
        """Schedule-route parity must be bit-identical to the generic
        engine's (the route is a perf choice, never a format one)."""
        import jax.numpy as jnp

        from ceph_tpu.utils import config

        n = 7 * 2048
        data = {
            i: jnp.asarray(rng.integers(0, 256, (n,), np.uint8))
            for i in range(4)
        }
        sched = lib_codec.encode_chunks(dict(data))
        with config.override(ec_use_sched=False):
            engine = lib_codec.encode_chunks(dict(data))
        for i in sched:
            np.testing.assert_array_equal(
                np.asarray(sched[i]), np.asarray(engine[i])
            )

    def test_sched_disabled_falls_back(self, rng, lib_codec):
        import jax.numpy as jnp

        from ceph_tpu.utils import config

        n = 7 * 2048
        data = {
            i: jnp.asarray(rng.integers(0, 256, (n,), np.uint8))
            for i in range(4)
        }
        before = _snap()
        with config.override(ec_use_sched=False):
            lib_codec.encode_chunks(data)
        d = _delta(before, _snap())
        assert d.get("sched_encode", 0) == 0
        assert d.get("einsum_encode", 0) >= 1

    def test_host_routes_counted(self, rng, lib_codec):
        before = _snap()
        data = {
            i: rng.integers(0, 256, (7 * 64,), np.uint8) for i in range(4)
        }
        parity = lib_codec.encode_chunks(data)
        assert all(isinstance(p, np.ndarray) for p in parity.values())
        chunks = dict(data) | parity
        del chunks[1], chunks[5]
        out = lib_codec.decode_chunks({1, 5}, chunks)
        d = _delta(before, _snap())
        assert d.get("host_encode", 0) >= 1
        assert d.get("host_decode", 0) >= 1
        np.testing.assert_array_equal(
            np.asarray(out[1]), np.asarray(data[1])
        )
        np.testing.assert_array_equal(
            np.asarray(out[5]), np.asarray(parity[5])
        )


# ------------------------------------------------------ the route table
# One row per combination that can occur: which route
# ``BitplaneDispatchMixin._plan_route`` names, and which ``ec_dispatch``
# counters move when the op then runs (exactly these, besides
# ``dispatches``, the ``*_bytes`` and the step timers). The kernels of
# the TPU routes run in the Pallas interpreter under the patched
# ``on_tpu``. ``ec_host_dispatch_bytes`` is 16 KiB here, so "small" is
# 8 KiB of shards and "big" 32 KiB. Meshes: "dp2" is (dp 2, sp 2) and
# splits any even batch or lane axis; "dp3" is (dp 3, sp 1) and
# refuses every shape below.
_HOST_LIMIT = 16384


def _route_case(name, route, moved, **kw):
    return pytest.param(route, set(moved), kw, id=name)


ROUTE_TABLE = [
    # -- dense GF matrix (isa 4+2), encode
    _route_case("host-small", "host", {"host_encode"}, staged="host"),
    _route_case("host-big-tileable", "pallas", {"pallas_encode"},
                staged="host", n=8192),
    _route_case("host-big-untileable", "einsum",
                {"pallas_fallback", "einsum_encode"},
                staged="host", n=8192 + 128),
    _route_case("host-big-off-tpu", "einsum", {"einsum_encode"},
                staged="host", n=8192, tpu=False),
    _route_case("host-threshold-zero", "pallas", {"pallas_encode"},
                staged="host", cfg={"ec_host_dispatch_bytes": 0}),
    _route_case("device-shards-form", "pallas_shards", {"pallas_encode"},
                lead=(8,)),
    _route_case("device-stacked", "pallas", {"pallas_encode"}),
    _route_case("device-untileable", "einsum",
                {"pallas_fallback", "einsum_encode"}, n=2048 + 128),
    _route_case("device-off-tpu", "einsum", {"einsum_encode"}, tpu=False),
    _route_case("device-pallas-off", "einsum", {"einsum_encode"},
                cfg={"ec_use_pallas": False}),
    # -- a mesh installed
    _route_case("mesh-over-host", "mesh", {"mesh_encode"},
                mesh="dp2", staged="host"),
    _route_case("mesh-over-shards-form", "mesh", {"mesh_encode"},
                mesh="dp2", lead=(8,)),
    _route_case("mesh-off-tpu", "mesh", {"mesh_encode"},
                mesh="dp2", tpu=False),
    _route_case("mesh-refuses-host-small", "host", {"host_encode"},
                mesh="dp3", staged="host"),
    _route_case("mesh-refuses-stacked", "pallas",
                {"mesh_fallback", "pallas_encode"}, mesh="dp3"),
    _route_case("mesh-refuses-shards-form", "pallas_shards",
                {"pallas_encode"}, mesh="dp3", lead=(8,)),
    _route_case("mesh-refuses-untileable", "einsum",
                {"mesh_fallback", "pallas_fallback", "einsum_encode"},
                mesh="dp3", n=2048 + 128),
    _route_case("mesh-switched-off", "host", {"host_encode"},
                mesh="dp2", staged="host", cfg={"ec_use_mesh": False}),
    # -- decode and delta ride the same ladder
    _route_case("decode-host-small", "host", {"host_decode"},
                op="decode", staged="host"),
    _route_case("decode-device", "pallas", {"pallas_decode"},
                op="decode", lost=(1, 2)),
    # isa's first parity row is all ones: one lost data chunk decodes
    # by a 0/1 row, which is the schedule kernel's
    _route_case("decode-one-loss-is-an-xor-row", "sched_shards",
                {"sched_decode"}, op="decode"),
    _route_case("decode-mesh", "mesh", {"mesh_decode"},
                op="decode", mesh="dp2"),
    _route_case("delta-host-small", "host", {"host_delta"},
                op="delta", staged="host"),
    _route_case("delta-device", "pallas", {"pallas_delta"}, op="delta"),
    _route_case("delta-mesh-refuses", "einsum",
                {"mesh_fallback", "einsum_delta"},
                op="delta", mesh="dp3", tpu=False),
    # -- sparse 0/1 byte matrix (xor 4+1): whole-chunk schedule, w=1
    _route_case("xor-sched-shards", "sched_shards", {"sched_encode"},
                codec="xor", lead=(8,)),
    _route_case("xor-sched-shape-rejected", "einsum",
                {"sched_rejected_shape", "pallas_fallback",
                 "einsum_encode"},
                codec="xor", n=2048 + 64),
    _route_case("xor-sched-is-a-tpu-kernel", "einsum", {"einsum_encode"},
                codec="xor", tpu=False),
    _route_case("xor-sched-off", "pallas_shards", {"pallas_encode"},
                codec="xor", lead=(8,), cfg={"ec_use_sched": False}),
    _route_case("xor-host-small", "host", {"host_encode"},
                codec="xor", staged="host"),
    _route_case("xor-mesh", "mesh", {"mesh_encode"},
                codec="xor", mesh="dp2", lead=(8,)),
    _route_case("xor-decode-sched", "sched_shards", {"sched_decode"},
                codec="xor", op="decode", lead=(8,)),
    # -- packet 0/1 matrix (liberation 4+2, w=7)
    _route_case("packet-sched-shards", "sched_shards", {"sched_encode"},
                codec="packet", n=7 * 2048),
    _route_case("packet-sched-stacked-off-tpu", "sched", {"sched_encode"},
                codec="packet", n=7 * 2048, tpu=False),
    _route_case("packet-sched-shape-rejected", "einsum",
                {"sched_rejected_shape", "einsum_encode"},
                codec="packet", n=7 * 1000, tpu=False),
    _route_case("packet-sched-density-rejected", "einsum",
                {"sched_rejected_density", "einsum_decode"},
                codec="packet", op="decode", lost=(0, 1), n=7 * 2048,
                tpu=False, cfg={"ec_sched_opt": False}),
    _route_case("packet-host-small", "host", {"host_encode"},
                codec="packet", staged="host", n=7 * 64),
    _route_case("packet-mesh", "mesh", {"mesh_encode"},
                codec="packet", mesh="dp2", n=7 * 2048, tpu=False),
    # -- fused encode+csum asked for
    _route_case("fused-stacked", "fused", {"fused_encode"},
                fused=True, staged="host", lead=(2,), n=4096),
    _route_case("fused-shards-form", "fused_shards", {"fused_encode"},
                fused=True, lead=(8,), n=4096),
    _route_case("fused-outranks-host-tables", "fused", {"fused_encode"},
                fused=True, staged="host"),
    _route_case("fused-untileable", None, {"fused_fallback"},
                fused=True, staged="host", n=2048 + 256),
    _route_case("fused-mesh-owns-the-shape", "mesh", set(),
                fused=True, mesh="dp2", staged="host", lead=(2,), n=4096),
    _route_case("fused-off-tpu", None, set(),
                fused=True, staged="host", tpu=False),
    _route_case("fused-interpreter-off-tpu", "fused", {"fused_encode"},
                fused=True, staged="host", tpu=False,
                cfg={"ec_fused_csum_interpret": True}),
    _route_case("fused-switched-off", None, set(),
                fused=True, staged="host", cfg={"ec_fused_csum": False}),
    # -- the ISA Cauchy (10,4) pool's write (``cauchy104-1m.write``):
    # 26 stripes of k = 10 rows. The write's rows come from the host
    # and go stacked; 26 is no multiple of the shards form's 8-stripe
    # block, so shards that are on the device are stacked too; a
    # multiple of 8 stripes of them rides the shards form at k = 10
    _route_case("fused-stacked-k10", "fused", {"fused_encode"},
                codec="cauchy104", fused=True, staged="host", lead=(26,),
                n=4096),
    _route_case("fused-k10-shards-of-26-stripes-go-stacked", "fused",
                {"fused_encode"},
                codec="cauchy104", fused=True, lead=(26,), n=4096),
    _route_case("fused-shards-form-k10", "fused_shards", {"fused_encode"},
                codec="cauchy104", fused=True, lead=(8,), n=4096),
]

_ROUTE_CODECS = {
    "dense": ("isa", {"k": "4", "m": "2"}),
    "xor": ("xor", {"k": "4"}),
    "cauchy104": ("isa", {"technique": "cauchy", "k": "10", "m": "4"}),
    "packet": (
        "jerasure",
        {"technique": "liberation", "k": "4", "m": "2", "w": "7"},
    ),
}


def _route_mesh(kind):
    import jax
    from jax.sharding import Mesh

    from ceph_tpu.parallel import make_ec_mesh

    if kind == "dp2":
        return make_ec_mesh(4, k=4)
    return Mesh(np.array(jax.devices()[:3]).reshape(3, 1), ("dp", "sp"))


@pytest.mark.parametrize("route, moved, case", ROUTE_TABLE)
def test_route_table(rng, monkeypatch, route, moved, case):
    import contextlib

    import jax.numpy as jnp

    from ceph_tpu.codecs.matrix_codec import BitplaneDispatchMixin
    from ceph_tpu.parallel import use_mesh
    from ceph_tpu.utils import config

    op = case.get("op", "encode")
    shape = case.get("lead", ()) + (case.get("n", 2048),)
    on_host = case.get("staged", "device") == "host"
    codec = registry.factory(*_ROUTE_CODECS[case.get("codec", "dense")])
    k, m = codec.k, codec.m
    place = (lambda a: a) if on_host else jnp.asarray

    def host_encode(d):
        return {
            i: np.asarray(p) for i, p in codec.encode_chunks(d).items()
        }

    # what the op under test consumes and must produce, made on the
    # default routes before anything is patched
    data = {i: rng.integers(0, 256, shape, np.uint8) for i in range(k)}
    parity = want = host_encode(data)
    if op == "decode":
        lost = case.get("lost", (1,))
        chunks = {
            i: place(v) for i, v in (data | parity).items()
            if i not in lost
        }
        want = {i: data[i] for i in lost}
    elif op == "delta":
        new1 = rng.integers(0, 256, shape, np.uint8)
        delta = {1: place(np.bitwise_xor(data[1], new1))}
        old_parity = {i: place(p) for i, p in parity.items()}
        want = host_encode(data | {1: new1})
    data = {i: place(v) for i, v in data.items()}

    asked = []
    real = BitplaneDispatchMixin._plan_route

    def spy(self, *args, **held):
        asked.append((args, held, real(self, *args, **held)))
        return asked[-1][2]

    monkeypatch.setattr(BitplaneDispatchMixin, "_plan_route", spy)
    monkeypatch.setattr(
        platform, "on_tpu", lambda: case.get("tpu", True)
    )
    mesh = case.get("mesh")
    with contextlib.ExitStack() as stack:
        stack.enter_context(config.override(
            **{"ec_host_dispatch_bytes": _HOST_LIMIT, **case.get("cfg", {})}
        ))
        if mesh:
            stack.enter_context(use_mesh(_route_mesh(mesh)))
        before = _snap()
        if case.get("fused"):
            got, _csums = codec.encode_chunks_with_csums(data, 256)
            assert (got is None) == (route not in ("fused", "fused_shards"))
        elif op == "encode":
            got = codec.encode_chunks(data)
        elif op == "decode":
            got = codec.decode_chunks(set(lost), chunks)
        else:
            got = codec.apply_delta(delta, old_parity)
        after = _snap()
        # the planner again, on the same question: the same answer, and
        # asking moves nothing
        args, held, answer = asked[0]
        assert real(codec, *args, **held) == answer
        assert _snap() == after
    assert len(asked) == 1, "one route decision a dispatch"
    assert answer[0] == route
    steps = {"dispatches", "prep_seconds", "h2d_seconds",
             "launch_seconds", "fetch_seconds"}
    d = _delta(before, after)
    assert {
        key for key in d
        if key not in steps and not key.endswith("_bytes")
    } == moved
    assert all(d[key] == 1 for key in moved)
    if got is not None:  # whatever the route, the same bytes
        for i, w in want.items():
            np.testing.assert_array_equal(np.asarray(got[i]), w)
