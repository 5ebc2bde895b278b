"""The cell ``rs84-rbd.randwrite`` (PR 26) as data: its mix parses and
sends nothing but patches once the image is preloaded, its per-layer
metrics read the counters they name, and ``correct`` turns false when a
parity page that a patch rewrote is tampered with in a store."""

import fnmatch
import time

import pytest

from benchmark import files, metrics
from benchmark.traffic import generator as G

from .helpers import PRINT_COUNTER_NAMES, counters_and_readings, run_cell
from .test_correct import check_numbers
from .test_generator import DictIo

CELL = "rs84-rbd.randwrite"
NEW_METRICS = [
    "parity_delta_pct", "rmw_read_ms", "delta_apply_ms",
    "delta_ops_per_dispatch", "delta_pad_pct", "coalesced_op_pct",
    "write_p95_ms",
]
#: of those, the ones whose counters only this PR's program has
NEW_COUNTERS = [
    "rmw_read_ms", "delta_apply_ms", "delta_ops_per_dispatch",
    "delta_pad_pct",
]


def entry(name: str) -> dict:
    return next(
        m for m in files.benchmark_json()["per_layer"] if m["name"] == name
    )


def test_the_mix_is_fio_randwrite_as_far_as_the_generator_goes():
    mix = files.mix("randwrite")
    assert mix["arrival"] == "closed"
    assert mix["rmw_max_len"] == 4096
    assert mix["classes"] == [
        {"name": "rand_overwrite", "op": "write_patch", "weight": 1}
    ]
    cell, config = files.cell(CELL), files.config("rs84-rbd")
    assert (cell["preload_objects"], config["queue_depth"]) == (128, 32)
    assert config["object_size"] == 4 << 20
    assert config["pool"] == files.config("rs84-4m")["pool"]
    assert cell["standing_fault"] is None and cell["chips"] == 1
    assert "length_law" in config["assumed"]
    assert set(config["reduced"]) == {
        "osd_hosts", "store", "working_set_objects", "scheduled_scrubs",
    }


def test_with_the_image_preloaded_a_client_sees_patches_only():
    """128 objects under 32 in flight: the generator always finds an
    object that is not busy, so it never falls back to a create (which
    at 4 MiB would swamp ``client_mbs``)."""
    size, seed = 16384, 3000000019
    io = DictIo()
    cell, config = files.cell(CELL), files.config("rs84-rbd")
    loader = G.Generator(
        io, files.mix("write"), size, config["queue_depth"], seed,
        limit=cell["preload_objects"],
    )
    gen = G.Generator(
        io, files.mix("randwrite"), size, config["queue_depth"], seed,
        limit=2000,
    )
    for g in (loader, gen):
        if g is gen:
            g.adopt(loader)
        g.start()
        deadline = time.monotonic() + 60
        while g.completed() < g.limit and time.monotonic() < deadline:
            time.sleep(0.01)
        g.close()
        assert g.completed() == g.limit
    assert {s.kind for s in gen.samples} == {"write_patch"}
    assert {s.cls for s in gen.samples} == {"rand_overwrite"}
    assert all(s.ok for s in gen.samples)
    assert len(gen.objects) == 128  # no object was created
    lengths = [s.nbytes for s in gen.samples]
    assert 1 <= min(lengths) and max(lengths) <= 4096
    assert max(lengths) > 2048  # the cap is this mix's, not the default
    for idx, st in gen.objects.items():
        want = G.expected_image(
            seed, idx, st.version, st.n_patches, size, gen.max_patch
        )
        assert bytes(io.objects[gen.oid(idx)]) == want


@pytest.mark.parametrize("name", NEW_METRICS)
def test_metric_file_agrees_with_its_entry(name):
    spec, listed = files.metric(name), entry(name)
    for key in ("name", "unit", "better", "source", "layer", "moves"):
        assert spec[key] == listed[key], key
    assert CELL in listed["workloads"]
    assert spec["reader"] in ("counter_ratio", "latency_tail")


def test_the_metric_files_read_a_recorded_counter_delta():
    """A window's counter deltas as the program's sets name them."""
    moved = {
        "osd.3.loadpool.1.rmw:parity_delta_ops": 90.0,
        "osd.4.loadpool.7.rmw:parity_delta_ops": 9.0,
        "osd.4.loadpool.7.rmw:full_stripe_ops": 1.0,
        "osd.3.loadpool.1.rmw:rmw_read_ops": 99.0,
        "osd.3.loadpool.1.rmw:rmw_read_seconds": 9.9,
        "osd.3.loadpool.1.rmw:delta_ops": 99.0,
        "osd.3.loadpool.1.rmw:delta_apply_seconds": 0.495,
        "ec_stream:delta_batches": 40.0,
        "ec_stream:delta_batch_ops": 99.0,
        "ec_stream:delta_batch_units": 150.0,
        "ec_stream:delta_pad_units": 50.0,
        "osd.3.coalesce:op_coalesced": 60.0,
        "loadgen_client:op_completed": 100.0,
    }
    ctx = metrics.RunContext(
        cell={}, config={}, device_kind="cpu", moved=moved, compiles=[],
        trace=None, window_s=1.0,
    )
    want = {
        "parity_delta_pct": 99.0, "rmw_read_ms": 100.0,
        "delta_apply_ms": 5.0, "delta_ops_per_dispatch": 2.475,
        "delta_pad_pct": 25.0, "coalesced_op_pct": 60.0,
    }
    for name, value in want.items():
        assert metrics.read(files.metric(name), ctx) == pytest.approx(value)


def test_a_program_without_the_counters_leaves_the_new_metrics_out():
    """What the parent commit gives in this cell: none of the delta or
    read-wait counters, so no reading, and no error (not 0.0)."""
    ctx = metrics.RunContext(
        cell={}, config={}, device_kind="cpu",
        moved={"loadgen_client:op_completed": 10.0,
               "osd.3.loadpool.1.rmw:parity_delta_ops": 10.0,
               "osd.3.loadpool.1.rmw:write_ops": 10.0,
               "ec_dispatch:host_delta": 10.0,
               "ec_stream:ops": 0.0},
        compiles=[], trace=None, window_s=1.0,
    )
    for name in NEW_COUNTERS:
        assert metrics.read(files.metric(name), ctx) is None


def test_write_p95_needs_a_hundred_patches():
    spec = files.metric("write_p95_ms")
    assert (spec["kinds"], spec["percentile"], spec["min_samples"]) == (
        ["write_patch"], 95, 100
    )
    sample = lambda ms, kind="write_patch": G.Sample(  # noqa: E731
        "rand_overwrite", kind, 0, 1, 0.0, ms / 1e3, ok=True
    )
    ctx = metrics.RunContext(
        cell={}, config={}, device_kind="cpu", moved={}, compiles=[],
        trace=None, window_s=1.0,
        samples=[sample(i) for i in range(1, 100)] + [sample(9e3, "read")],
    )
    assert metrics.read(spec, ctx) is None
    ctx.samples.append(sample(100))
    assert metrics.read(spec, ctx) == pytest.approx(95.0)


@pytest.fixture(scope="module")
def rehearsal():
    """(counter names over the traced window, readings) of the cell."""
    code, last, text, _took = run_cell(
        CELL, trace=1, prelude=PRINT_COUNTER_NAMES
    )
    assert code == 0 and last["correct"], text
    return counters_and_readings(text)


@pytest.mark.parametrize("name", NEW_METRICS)
def test_the_program_has_the_counters_and_the_cell_reads_them(name, rehearsal):
    names, readings = rehearsal
    spec = files.metric(name)
    for pattern in spec.get("numerator", []) + spec.get("denominator", []):
        assert any(fnmatch.fnmatchcase(n, pattern) for n in names), (
            f"{name}: no counter matches {pattern!r}"
        )
    if name != "write_p95_ms":  # a 4 s rehearsal has no hundred patches
        assert isinstance(readings.get(name), float), readings
        assert readings[name] >= 0


def test_rehearsal_sends_deltas_through_the_batched_entry(rehearsal):
    _names, readings = rehearsal
    assert readings["parity_delta_pct"] > 90
    assert readings["delta_ops_per_dispatch"] >= 1
    assert readings["compiles_in_window"] == 0


#: after the window, in a store: one byte of a parity page that the
#: last patch of a patched object rewrote
TAMPER_PATCHED_PARITY = '''
import benchmark.check as C
from benchmark.traffic import generator as G
from ceph_tpu.store.transaction import Transaction
_check = C.check
def check(cluster, gen, config, seed, count):
    pool = config["pool"]
    idx = next(
        i for i in C.sample_objects(gen, seed, count)
        if gen.objects[i].n_patches
    )
    st = gen.objects[idx]
    off, _patch = G.patch_bytes(
        gen.seed, idx, st.version, st.n_patches, gen.object_size,
        gen.max_patch,
    )
    page = off // (pool["k"] * pool["chunk_size"]) * pool["chunk_size"]
    oid = gen.oid(idx)
    acting = cluster.mon.osdmap.object_to_acting(cluster.pool, oid)
    shard = next(s for s in (9, 10, 11, 8) if acting[s] >= 0)
    store = cluster.stores[acting[shard]]
    key = C._shard_keys(store)[(oid, shard)]
    byte = bytes([store.read(key)[page + 100] ^ 1])
    store.queue_transactions(Transaction().write(key, page + 100, byte))
    print("TAMPERED", oid, "shard", shard, "page at", page, flush=True)
    return _check(cluster, gen, config, seed, count)
C.check = check
'''


def test_a_tampered_parity_page_of_a_patched_object_is_caught():
    code, last, out, _took = run_cell(CELL, prelude=TAMPER_PATCHED_PARITY)
    assert "TAMPERED" in out, out
    assert last is not None and last["correct"] is False, out
    assert code != 0
    numbers = check_numbers(last)
    assert numbers["shard_mismatch"] >= 1, numbers
    assert numbers["read_mismatch"] == 0 and numbers["ledger_gap"] == 0


# ----- the warm-up waits for the first device delta batch (PR 32, refusal
# round: one run in thirteen on the chip ended its warm-up in a quiet
# stretch before that batch, and its four programs compiled in the window)
def test_the_cell_waits_for_its_kernel_counter_and_a_rehearsal_does_not():
    from benchmark import run as R

    cell = files.cell(CELL)
    assert cell["warmup"]["moved"] == [cell["codec_kernel"]["counter"]]
    R.apply_rehearsal(cell, files.config(cell["config"]), files.mix("randwrite"))
    assert "moved" not in cell["warmup"]


class _Gen:
    def completed(self) -> int:
        return 10**6


class _Log:
    events: list = []

    def last(self) -> float:
        return 0.0

    def total_seconds(self) -> float:
        return 0.0


@pytest.mark.parametrize("moved", [[], ["set:key"]])
def test_warm_up_ends_only_once_the_listed_counters_have_moved(
    monkeypatch, moved
):
    from benchmark import clock, counters
    from benchmark import run as R

    t0 = time.monotonic()
    # the counter moves 0.4 s in; ops and quiet are there from the start
    monkeypatch.setattr(
        counters, "snapshot",
        lambda: {"set:key": 5 + (time.monotonic() - t0 > 0.4)},
    )
    cell = {
        "warmup": {"min_ops": 1, "quiet_s": 0.0, "moved": moved},
        "deadlines_s": {"warmup": 5},
    }
    R.warm_up(_Gen(), _Log(), cell)
    took = time.monotonic() - t0
    assert (took >= 0.4) == bool(moved), took
    # a counter that never moves is a missed deadline that names it
    cell = {
        "warmup": {"min_ops": 1, "quiet_s": 0.0, "moved": ["set:other"]},
        "deadlines_s": {"warmup": 0.2},
    }
    with pytest.raises(clock.DeadlineMissed, match="set:other"):
        R.warm_up(_Gen(), _Log(), cell)
