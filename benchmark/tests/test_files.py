"""Every entry of BENCHMARK.json resolves to its files, and every name
and unit keeps to the allowed characters."""

import json
import os
import re

import pytest

from benchmark import files
from benchmark.metrics import readers  # noqa: F401  (package exists)
from benchmark.traffic.generator import OP_KINDS

from .helpers import ROOT

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
BENCH = files.benchmark_json()
CELLS = [w["name"] for w in BENCH["workloads"]]
METRICS = BENCH["end_to_end"] + BENCH["per_layer"]


def test_keys_are_exactly_the_contracts():
    assert set(BENCH) == {
        "command", "paths", "run_seconds", "configs", "workloads",
        "end_to_end", "per_layer",
    }
    assert 1 <= BENCH["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 64 * 1024


@pytest.mark.parametrize("cell", CELLS)
def test_cell_resolves(cell):
    spec = files.cell(cell)
    config = files.config(spec["config"])
    mix = files.mix(spec["traffic"])
    assert config["name"] == spec["config"]
    assert all(c["op"] in OP_KINDS for c in mix["classes"])
    assert set(spec["deadlines_s"]) == {
        "boot", "preload", "fault", "warmup", "drain", "check", "shutdown",
    }
    # the client's whole patience for one op fits the drain deadline
    client = spec["client"]
    assert (
        client["op_timeout_s"] * client["max_attempts"]
        <= spec["deadlines_s"]["drain"]
    )
    assert len(spec["why"]) <= 200 and "\n" not in spec["why"]


@pytest.mark.parametrize("cell", CELLS)
def test_whole_run_alarm_is_well_under_the_drivers_clock(cell):
    from benchmark import run

    alarm = run.alarm_seconds(files.cell(cell), BENCH["run_seconds"])
    assert alarm <= 300, alarm  # the driver stopped PR 22 at 360 s


@pytest.mark.parametrize("cell", CELLS)
def test_cell_reports_what_the_contract_asks(cell):
    e2e = [m["name"] for m in files.metrics_for(cell, "end_to_end")]
    assert "setup_s" in e2e and len(e2e) >= 2
    layer = files.metrics_for(cell, "per_layer")
    assert layer
    for m in layer:
        assert m["moves"] in e2e, (m["name"], m["moves"])


@pytest.mark.parametrize("metric", BENCH["per_layer"], ids=lambda m: m["name"])
def test_per_layer_metric_has_its_file_and_reader(metric):
    spec = files.metric(metric["name"])
    for key in ("name", "layer", "unit", "better", "source", "moves"):
        assert spec[key] == metric[key], key
    # which cells report it is BENCHMARK.json's alone to say
    assert "workloads" not in spec
    path = os.path.join(
        files.HERE, "metrics", "readers", spec["reader"] + ".py"
    )
    assert os.path.exists(path)
    assert set(metric) <= {
        "name", "unit", "better", "source", "layer", "moves", "workloads",
    }


def test_names_and_units_use_only_the_allowed_characters():
    names = (
        [m["name"] for m in METRICS] + CELLS
        + [c["name"] for c in BENCH["configs"]]
        + [w["traffic"] for w in BENCH["workloads"]]
        + [k for c in BENCH["configs"] for k in c["reduced"]]
    )
    for name in names:
        assert NAME.match(name), name
    assert len(set(CELLS)) == len(CELLS)
    assert len({m["name"] for m in METRICS}) == len(METRICS)
    for m in METRICS:
        assert UNIT.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
        assert m["source"] in (
            "device_trace", "program_span", "program_counter", "host_clock",
        )
    for m in BENCH["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25


def test_configs_and_chips():
    used = {w["config"] for w in BENCH["workloads"]}
    for c in BENCH["configs"]:
        assert c["name"] in used
        assert c["file"].startswith("benchmark/")
        assert len(c["source"]) <= 200
        with open(os.path.join(ROOT, c["file"])) as f:
            body = json.load(f)
        assert body["source"] == c["source"]
        assert set(c["reduced"]) == set(body["reduced"])
    four = [w for w in BENCH["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(BENCH["workloads"]) // 2)
    assert all(w["chips"] in (1, 4) for w in BENCH["workloads"])


def test_files_under_paths_are_named_from_allowed_characters():
    allowed = re.compile(r"^[A-Za-z0-9_.\-/]+$")
    for base, dirs, names in os.walk(files.HERE):
        dirs[:] = [d for d in dirs if d not in ("__pycache__", ".trace")]
        for name in names:
            rel = os.path.relpath(os.path.join(base, name), ROOT)
            assert allowed.match(rel), rel
