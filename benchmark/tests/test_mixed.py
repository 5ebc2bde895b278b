"""The cell ``rs84-64k.mixed`` (PR 32) as data: the mix is the small
objects fragment's kinds over a set of names, the cell is
``rs84-64k``'s with a preload at the population the mix settles at, and
every per-layer metric that will list the cell finds the counters it
names in a traced rehearsal and gives a number there. The cell is
queued (``queued_cells.json``; PERF.md, Open questions): its entries
are not in ``BENCHMARK.json`` yet, and these tests run it as it will
run once they are."""

import fnmatch
import json
import os

import pytest

from benchmark import files

from .helpers import (
    PRINT_COUNTER_NAMES, QUEUED, counters_and_readings, queued_cells_listed,
    run_cell, with_queued_cells,
)

pytestmark = pytest.mark.usefixtures(queued_cells_listed.__name__)

CELL, CONFIG, MIX = "rs84-64k.mixed", "rs84-64k", "mixed"
TAIL = "write_p95_ms.rs84-64k.mixed"
#: read from the device's trace: a CPU rehearsal has none
DEVICE_METRICS = {"codec_roofline", "device_idle_pct"}
LISTED = [
    m["name"]
    for m in with_queued_cells(files.benchmark_json())["per_layer"]
    if CELL in m.get("workloads", [CELL])
    and m["name"] not in DEVICE_METRICS
]


def test_the_cell_is_queued_whole_or_listed_whole():
    with open(os.path.join(files.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)  # the file, not this test's enlarged copy
    lists = {
        m["name"] for m in bench["per_layer"]
        if CELL in m.get("workloads", [])
    }
    if CELL in [w["name"] for w in bench["workloads"]]:
        # a later PR listed it: with every entry that was queued
        assert lists == set(QUEUED[CELL]["per_layer_lists"]) | {TAIL}
    else:
        assert not lists
        assert TAIL not in [m["name"] for m in bench["per_layer"]]


def test_the_mix_is_the_small_objects_fragment_over_a_set_of_names():
    mix, config = files.mix(MIX), files.config(CONFIG)
    assert [(c["name"], c["op"], c["weight"]) for c in mix["classes"]] == [
        ("read", "read", 4), ("append", "append", 4),
        ("delete", "delete", 2),
    ]
    assert mix["names"] == 1024
    # one stripe: an EC pool's write alignment, and whole-chunk shards
    # for check.py
    stripe = config["pool"]["k"] * config["pool"]["chunk_size"]
    assert mix["append_len"] == stripe == 32768
    assert mix["names"] >= config["queue_depth"]
    assert "ec-small-objects.yaml" in mix["source"]
    assert {"source_wording", "ratio", "op_on_the_wire", "append_len",
            "lengths"} <= set(mix["assumed"])


def test_the_cell_is_rs84_64k_with_half_the_names_preloaded():
    cell = files.cell(CELL)
    listed = next(
        w for w in files.benchmark_json()["workloads"] if w["name"] == CELL
    )
    for key in ("config", "traffic", "chips", "why"):
        assert cell[key] == listed[key], key
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, MIX, 1
    )
    assert len(cell["why"]) <= 200 and "one issuer" in cell["why"]
    # appends and deletes of a name come at the same rate, so half the
    # names exist, at the configuration's object size on average
    assert cell["preload_objects"] == files.mix(MIX)["names"] // 2
    assert cell["standing_fault"] is None
    base = files.cell("rs84-64k.write")
    assert cell["deadlines_s"] == {**base["deadlines_s"], "preload": 35}
    assert cell["client"] == base["client"]
    assert cell["warmup"] == base["warmup"]
    assert cell["codec_kernel"] == base["codec_kernel"]
    assert cell["rehearse"]["names"] >= files.config(CONFIG)["queue_depth"]
    assert cell["rehearse"]["preload_objects"] <= cell["rehearse"]["names"]


def test_the_appends_tail_is_a_file_of_its_own():
    spec = files.metric(TAIL)
    listed = next(
        m for m in files.benchmark_json()["per_layer"] if m["name"] == TAIL
    )
    for key in ("name", "unit", "better", "source", "layer", "moves"):
        assert spec[key] == listed[key], key
    assert listed["workloads"] == [CELL]
    assert (spec["reader"], spec["kinds"], spec["percentile"]) == (
        "latency_tail", ["append"], 95
    )
    assert spec["min_samples"] == 100
    # the entry that was there keeps its cell and its kind
    assert files.metric("write_p95_ms")["kinds"] == ["write_patch"]


def test_the_cell_lists_what_its_ops_run():
    assert {
        "ec_write_assemble_ms", "ec_write_encode_ms", "ec_write_txn_ms",
        "ec_write_fanout_ms", "subop_wait_ms", "store_txn_ms",
        "fanouts_per_write", "store_read_ms", "read_gather_ms",
        "read_finish_ms", "read_p95_ms", "coalesced_op_pct", "ring_op_pct",
        "ring_ops_per_batch", "ring_pad_pct", "ring_wait_ms", TAIL,
        "codec_device_pct", "compiles_in_window",
        # their counters move here (every read, every append), and the
        # share has to read 0: nothing is down, nothing is patched
        "decode_read_pct", "parity_delta_pct",
    } <= set(LISTED)
    # stages that no op of this mix enters have nothing to read
    assert not {"read_reconstruct_ms", "rmw_read_ms", "delta_apply_ms",
                "delta_ops_per_dispatch", "delta_pad_pct",
                "write_p95_ms"} & set(LISTED)


@pytest.fixture(scope="module")
def rehearsal():
    """(counter names over the traced window, readings, all output)."""
    code, last, text, _took = run_cell(
        CELL, trace=1, seconds=8.0, prelude=PRINT_COUNTER_NAMES
    )
    assert code == 0 and last["correct"], text
    return counters_and_readings(text)


@pytest.mark.parametrize("name", LISTED)
def test_every_listed_metric_finds_its_counters_and_reads(name, rehearsal):
    names, readings = rehearsal
    spec = files.metric(name)
    if spec["reader"] == "counter_ratio":
        for pattern in spec["numerator"] + spec["denominator"]:
            assert any(fnmatch.fnmatchcase(n, pattern) for n in names), (
                f"{name}: no counter matches {pattern!r}"
            )
    if name == TAIL and name not in readings:
        pytest.skip("fewer than 100 appends in a CPU rehearsal's window")
    assert isinstance(readings.get(name), float), (name, readings)
    assert readings[name] >= 0


def test_appends_ride_the_fused_kernel_and_the_ring(rehearsal):
    _names, readings = rehearsal
    assert readings["codec_device_pct"] == 100.0
    assert readings["fanouts_per_write"] == 1.0
    assert readings["ring_op_pct"] > 0
    # bypassed, and the line says so: no read decodes, no append patches
    assert readings["decode_read_pct"] == 0.0
    assert readings["parity_delta_pct"] == 0.0
