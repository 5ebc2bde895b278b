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


# ------------------------------------- a pool's reference, found by name
XOR_REFERENCE = '''
"""k data shards and their XOR: the plain reference of a k+1 pool."""
import numpy as np


def shards_of(obj, k, m, chunk_size, pool):
    assert m == 1 and pool["plugin"] == "xor"
    stripe = k * chunk_size
    n = -(-len(obj) // stripe)
    buf = np.zeros(n * stripe, np.uint8)
    buf[: len(obj)] = np.frombuffer(obj, np.uint8)
    data = buf.reshape(n, k, chunk_size).transpose(1, 0, 2).reshape(k, -1)
    return np.concatenate(
        [data, np.bitwise_xor.reduce(data, axis=0)[None]], axis=0
    )


def decode_data(shards, k, m):
    missing = [s for s in range(k) if s not in shards]
    out = {s: shards[s] for s in range(k) if s in shards}
    if missing:
        out[missing[0]] = np.bitwise_xor.reduce(
            np.stack(list(shards.values())), axis=0
        )
    return np.stack([out[s] for s in range(k)])


def object_from_data_shards(data, size, chunk_size):
    k = data.shape[0]
    return data.reshape(k, -1, chunk_size).transpose(1, 0, 2).reshape(
        -1
    )[:size].tobytes()
'''


def test_a_configuration_without_the_key_gets_rs_vandermonde():
    for c in BENCH["configs"]:
        config = files.config(c["name"])
        assert "reference" not in config["pool"]
        assert files.reference(config).name == "rs_vandermonde"
    ref = files.reference(files.config("rs84-64k"))
    shards = ref.shards_of(bytes(range(256)) * 256, 8, 4, 4096)
    assert shards.shape == (12, 8192)
    data = ref.decode_data({s: shards[s] for s in range(4, 12)}, 8, 4)
    assert ref.object_from_data_shards(data, 65536, 4096) == (
        bytes(range(256)) * 256
    )


def test_a_reference_is_a_file_and_gets_the_pool_where_it_asks(
    tmp_path, monkeypatch
):
    (tmp_path / "xor21.py").write_text(XOR_REFERENCE)
    monkeypatch.setattr(files, "REFERENCE_DIR", str(tmp_path))
    config = {"name": "toy", "pool": {
        "plugin": "xor", "k": 2, "m": 1, "reference": "xor21",
    }}
    ref = files.reference(config)
    assert ref.name == "xor21"
    shards = ref.shards_of(b"ab" * 8, 2, 1, 4)  # takes ``pool`` itself
    assert shards.shape == (3, 8)
    assert bytes(shards[2]) == bytes(
        a ^ b for a, b in zip(bytes(shards[0]), bytes(shards[1]))
    )
    data = ref.decode_data({1: shards[1], 2: shards[2]}, 2, 1)
    assert ref.object_from_data_shards(data, 16, 4) == b"ab" * 8


def test_a_name_with_no_file_or_a_file_short_of_a_function_says_which(
    tmp_path, monkeypatch
):
    monkeypatch.setattr(files, "REFERENCE_DIR", str(tmp_path))
    config = {"name": "toy", "pool": {"k": 2, "m": 1, "reference": "nope"}}
    with pytest.raises(FileNotFoundError, match="nope"):
        files.reference(config)
    (tmp_path / "half.py").write_text(
        "def shards_of(obj, k, m, chunk_size): ...\n"
    )
    config["pool"]["reference"] = "half"
    with pytest.raises(
        AttributeError, match="decode_data, object_from_data_shards"
    ):
        files.reference(config)


#: in the child, before ``main``: the cell's pool becomes a k=2 m=1 XOR
#: pool whose reference is a file in a directory of the test's own
TOY_POOL = '''
import benchmark.files as F
F.REFERENCE_DIR = {directory!r}
_config = F.config
def config(name):
    c = _config(name)
    c["pool"].update(plugin="xor", k=2, m=1, reference={reference!r})
    return c
F.config = config
'''


def test_the_check_uses_the_reference_the_configuration_names(tmp_path):
    from .helpers import run_cell

    (tmp_path / "xor21.py").write_text(XOR_REFERENCE)
    prelude = TOY_POOL.format(directory=str(tmp_path), reference="xor21")
    code, last, out, _took = run_cell("rs84-64k.write", prelude=prelude)
    assert code == 0 and last["correct"], out
    assert last["checked"]["shards"]["value"] == 3 * (
        last["checked"]["objects"]["value"]
    )
    # the same pool held against the Vandermonde code: every parity
    # shard differs (its first parity row is not all ones)
    prelude = TOY_POOL.format(directory=files.REFERENCE_DIR,
                              reference="rs_vandermonde")
    code, last, out, _took = run_cell("rs84-64k.write", prelude=prelude)
    assert code != 0 and last["correct"] is False, out
    assert last["checked"]["shard_mismatch"]["value"] >= 1
    # and a name with no file ends the run before the cluster boots
    prelude = TOY_POOL.format(directory=str(tmp_path), reference="nope")
    code, last, out, took = run_cell("rs84-64k.write", prelude=prelude)
    assert code != 0 and last is None
    assert "pool.reference is 'nope'" in out and "boot:" not in out


def test_files_under_paths_are_named_from_allowed_characters():
    allowed = re.compile(r"^[A-Za-z0-9_.\-/]+$")
    for base, dirs, names in os.walk(files.HERE):
        dirs[:] = [d for d in dirs if d not in ("__pycache__", ".trace")]
        for name in names:
            rel = os.path.relpath(os.path.join(base, name), ROOT)
            assert allowed.match(rel), rel
