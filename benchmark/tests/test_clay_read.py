"""The cell ``clay84-4m.degraded-read`` (PR 33) as data: its
configuration is the document's Clay (8,4,11) pool over ``rs84-4m``'s
cluster, its cell ``rs84-4m.degraded-read``'s traffic and fault, its
metrics read the counters they name and a program without them reads
nothing; it rehearses ``correct`` against ``reference/clay.py`` on the
CPU with every new metric in a traced rehearsal; and the pool's two own
controls (``control_clay.py``) turn ``correct`` false."""

import fnmatch

import pytest

from benchmark import files, metrics
from benchmark.trace import clay_cost

from . import control_clay
from .helpers import PRINT_COUNTER_NAMES, counters_and_readings, run_cell
from .test_correct import check_numbers

CELL, CONFIG = "clay84-4m.degraded-read", "clay84-4m"
COUNTER_METRICS = [
    "repair_read_pct", "repair_helper_ratio", "clay_repair_ms",
    "clay_gather_ms", "subread_extents_per_read", "clay_kernel_pct",
]
COPIES = [
    "read_p95_ms", "decode_read_pct", "read_gather_ms",
    "read_reconstruct_ms", "read_finish_ms", "store_read_ms",
]
OWN = COUNTER_METRICS + [c + "." + CONFIG for c in COPIES] + ["clay_roofline"]


def entry(group: str, name: str) -> dict:
    return next(
        e for e in files.benchmark_json()[group] if e["name"] == name
    )


def test_the_configuration_is_the_documents_pool_on_the_flagship_cluster():
    config, base = files.config(CONFIG), files.config("rs84-4m")
    listed = entry("configs", CONFIG)
    assert listed["file"] == "benchmark/configs/clay84-4m.json"
    assert config["source"] == listed["source"]
    assert len(listed["source"]) <= 200
    assert "erasure-code-clay.rst" in listed["source"]
    assert set(config["reduced"]) == set(listed["reduced"]) == set(
        base["reduced"]
    )
    pool = config["pool"]
    assert (pool["plugin"], pool["k"], pool["m"], pool["d"]) == (
        "clay", 8, 4, 11
    )
    assert pool["reference"] == "clay" and pool["chunk_size"] == 16384
    assert pool["pg_num"] == base["pool"]["pg_num"]
    assert config["cluster"] == base["cluster"]
    assert (config["object_size"], config["queue_depth"]) == (
        base["object_size"], base["queue_depth"]
    )
    # whole stripes, and sub-chunks the pair-transform kernels take
    geo = config["geometry"]
    assert geo["sub_chunk_no"] == (pool["d"] - pool["k"] + 1) ** geo["t"]
    assert pool["chunk_size"] == geo["sub_chunk_no"] * geo["sub_chunk_bytes"]
    assert geo["sub_chunk_bytes"] % 128 == 0
    assert config["object_size"] % (pool["k"] * pool["chunk_size"]) == 0
    assert config["guarantees"][2].startswith(
        "the k+m stored shards equal a plain reference's Clay (8,4,11)"
    )
    assert {
        "source_wording", "chunk_size", "pg_num", "degraded_reads_repair",
    } <= set(config["assumed"])


def test_the_cell_is_the_rs_cells_traffic_and_fault_on_the_clay_pool():
    cell, listed = files.cell(CELL), entry("workloads", CELL)
    base = files.cell("rs84-4m.degraded-read")
    for key in ("config", "traffic", "chips", "why"):
        assert cell[key] == listed[key], key
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "degraded-read", 1
    )
    assert len(cell["why"]) <= 200
    for key in (
        "preload_objects", "standing_fault", "client", "check_objects",
        "trace_window_s", "codec_kernel", "rehearse",
    ):
        assert cell[key] == base[key], key
    # the warm-up may not end before a repair has passed the warm that
    # compiles every lost chunk's program
    assert cell["warmup"]["moved"] == ["ec_dispatch:clay_kernel_bytes"]
    assert cell["clay_program"] == {
        "match": "jit_clay_repair", "line": "modules"
    }


def test_the_entries_are_listed_for_this_cell_alone():
    b = files.benchmark_json()
    assert CONFIG in [c["name"] for c in b["configs"]]
    assert CELL in [w["name"] for w in b["workloads"]]
    for name in OWN:
        assert entry("per_layer", name)["workloads"] == [CELL], name
    for name in COPIES:
        # the accepted entry is as it was: the RS cell's alone
        assert CELL not in entry("per_layer", name)["workloads"]
        copy, old = files.metric(name + "." + CONFIG), files.metric(name)
        assert {**copy, "name": name} == old


def test_the_reference_is_found_by_name_and_takes_the_pool():
    reference = files.reference(files.config(CONFIG))
    assert reference.name == "clay"
    assert reference.shards_of.keywords["pool"]["d"] == 11
    assert reference.decode_data.keywords["pool"]["chunk_size"] == 16384
    with open(files.REFERENCE_DIR + "/clay.py", encoding="utf-8") as f:
        assert "ceph_tpu" not in "".join(
            ln for ln in f if ln.lstrip().startswith(("import", "from"))
        )


@pytest.mark.parametrize("name", COUNTER_METRICS)
def test_a_counter_metric_reads_what_it_names(name):
    spec = files.metric(name)
    # a numerator newer than its denominator is read only where the
    # program has it
    assert spec["reader"] == (
        "counter_ratio_of" if spec["denominator"] == ["osd.*.read:read_ops"]
        else "counter_ratio"
    )
    moved = {
        "osd.3.loadpool.1.read:read_ops": 30,
        "osd.3.loadpool.1.read:repair_ops": 20,
        "osd.3.loadpool.1.read:repair_seconds": 0.5,
        "osd.3.loadpool.1.read:repair_gather_seconds": 0.05,
        "osd.3.loadpool.1.read:repair_helper_bytes": 11 * 4096,
        "osd.3.loadpool.1.read:repair_rebuilt_bytes": 4 * 4096,
        "osd.3.loadpool.1.read:subread_extents": 600,
        "ec_dispatch:clay_kernel_bytes": 300,
        "ec_dispatch:clay_fallback_bytes": 100,
    }
    want = {
        "repair_read_pct": 100 * 20 / 30, "repair_helper_ratio": 2.75,
        "clay_repair_ms": 25.0, "clay_gather_ms": 2.5,
        "subread_extents_per_read": 20.0, "clay_kernel_pct": 75.0,
    }[name]
    ctx = metrics.RunContext(
        cell={}, config={}, device_kind="x", moved=moved, compiles=[],
        trace=None, window_s=1.0,
    )
    assert metrics.read(spec, ctx) == pytest.approx(want)
    # a program without the counters (the parent) reports nothing
    ctx.moved = {"osd.3.loadpool.1.read:read_ops": 30}
    assert metrics.read(spec, ctx) is None


def test_clay_roofline_reads_the_program_runs_against_the_bytes_needed():
    from benchmark.trace import xplane

    config, cell = files.config(CONFIG), files.cell(CELL)
    helper, rebuilt = 11 * 131072 * 10, 524288 * 10
    trace = xplane.Trace(
        devices={}, host=[], modules={"/device:TPU:0": [
            ("jit_clay_repair(123)", i * 1.0, i * 1.0 + 100e-6)
            for i in range(10)
        ] + [("jit_other(5)", 50.0, 51.0)]},
    )
    ctx = metrics.RunContext(
        cell=cell, config=config, device_kind="TPU v5 lite", moved={
            "osd.1.loadpool.2.read:repair_helper_bytes": helper,
            "osd.1.loadpool.2.read:repair_rebuilt_bytes": rebuilt,
        }, compiles=[], trace=trace, window_s=30.0,
    )
    spec = files.metric("clay_roofline")
    least = (helper + rebuilt) / 819.0e9
    assert metrics.read(spec, ctx) == pytest.approx(100 * least / 1e-3)
    assert clay_cost.helper_ratio(8, 4, 11) == 2.75
    assert not [n for n in ctx.notes if "the code's design" in n]
    # no counters (the parent), no such program, no trace: nothing
    for broken in (
        {"moved": {}}, {"trace": None},
        {"cell": {**cell, "clay_program": {"match": "jit_absent"}}},
    ):
        other = metrics.RunContext(**{
            **{f: getattr(ctx, f) for f in (
                "cell", "config", "device_kind", "moved", "compiles",
                "trace", "window_s",
            )}, **broken,
        })
        assert metrics.read(spec, other) is None


@pytest.fixture(scope="module")
def traced_rehearsal():
    code, last, out, _took = run_cell(
        CELL, trace=1, prelude=PRINT_COUNTER_NAMES
    )
    assert code == 0 and last is not None and last["correct"], out
    return last, out


def test_the_cell_rehearses_correct_against_the_clay_reference(
    traced_rehearsal
):
    last, out = traced_rehearsal
    assert last["failed"] == 0 and last["attempted"] > 0
    numbers = check_numbers(last)
    assert not any(numbers.values()), numbers
    checked = last["checked"]
    assert checked["objects"]["value"] == 4
    assert checked["shards"]["value"] == 4 * 11  # one OSD is down


def test_every_new_metric_is_in_a_traced_rehearsal(traced_rehearsal):
    _last, out = traced_rehearsal
    names, readings = counters_and_readings(out)
    # (the CPU has no device events: the two rooflines read nothing)
    for name in OWN[:-1]:
        assert name in readings, (name, sorted(readings))
    assert readings["repair_helper_ratio"] == pytest.approx(2.75)
    assert 0 < readings["repair_read_pct"] <= 100
    assert readings["clay_kernel_pct"] == 100.0
    assert readings["repair_read_pct"] == pytest.approx(
        readings["decode_read_pct." + CONFIG], abs=10
    )
    for pattern in (
        "osd.*.read:repair_ops", "osd.*.read:repair_seconds",
        "osd.*.read:repair_gather_seconds",
        "osd.*.read:repair_helper_bytes",
        "osd.*.read:repair_rebuilt_bytes", "osd.*.read:subread_extents",
        "ec_dispatch:clay_kernel_bytes", "ec_dispatch:clay_fallback_bytes",
    ):
        assert fnmatch.filter(names, pattern), pattern


@pytest.mark.parametrize("name,number,reads_right", [
    ("wrong_pair", "shard_mismatch", True),
    ("repair_returns_helper", "read_mismatch", False),
])
def test_the_pools_own_controls_turn_correct_false(name, number, reads_right):
    code, last, out, _took = run_cell(
        CELL, prelude=control_clay.BREAKS[name]
    )
    assert last is not None, out
    assert last["correct"] is False and code != 0, out
    numbers = check_numbers(last)
    assert numbers[number] > 0, numbers
    if reads_right:
        # a code of its own: every read and repair still right, every
        # sampled object's stored parity not the reference's
        assert numbers["read_mismatch"] == 0 and last["failed"] == 0
        assert numbers["shard_mismatch"] >= last["checked"]["objects"]["value"]
    else:
        # the generator's own verification failed ops in the window
        assert last["failed"] > 0 and numbers["failed_ops"] > 0
        assert numbers["shard_mismatch"] == 0
