"""chip_smoke.py on the CPU: the device gate refuses, and the smoke's
own phases — imported as functions, tiny sizes, interpret-mode kernels
behind a faked TPU predicate — hold the same guarantee and route
assertions the chip run does."""

import os
import subprocess
import sys

import pytest

import chip_smoke
from ceph_tpu.utils import platform

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_gate_refuses_cpu_and_names_it():
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "chip_smoke.py")],
        cwd=ROOT, capture_output=True, text=True, timeout=120,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert proc.returncode != 0
    assert "platform='cpu'" in proc.stderr
    assert '"ok"' not in proc.stdout  # no result line on failure


@pytest.fixture
def tiny_run(monkeypatch):
    """Routes believe in a TPU; kernels run in the interpreter
    (``platform.pallas_interpret`` looks at the real backend)."""
    monkeypatch.setattr(platform, "on_tpu", lambda: True)
    run = chip_smoke.Run(chip_smoke.TINY, seed=0xEC, interpret=True)
    run.log.install()
    yield run
    run.log.uninstall()


def test_census_tiny(tiny_run):
    rows = chip_smoke.census(tiny_run)
    assert rows and all(r["status"] == "ok" for r in rows), rows
    families = {r["kernel"].split()[0] for r in rows}
    assert families == {"rs84", "sched", "clay(8,4,11)", "pallas_crc"}


def test_leg_a_tiny(tiny_run):
    result = chip_smoke.leg_a(tiny_run)
    c = result["counters"]
    assert c["ec.fused_encode"] > 0 and c["ec.pallas_decode"] > 0
    assert result["codec_bytes_device"] > 0


def test_leg_b_tiny(tiny_run):
    result = chip_smoke.leg_b(tiny_run)
    assert result["counters"]["ring.batches"] > 0
    assert (
        result["compiles_second_half"] <= result["compiles_first_half"]
    )


def test_leg_c_tiny(tiny_run):
    import jax

    assert chip_smoke.leg_c(tiny_run, 1) == {
        "leg": "C", "skipped": "1 device"
    }
    result = chip_smoke.leg_c(tiny_run, len(jax.devices()))
    assert result["mesh_output_devices"] == 4
    assert result["counters"]["ec.mesh_encode"] > 0


@pytest.fixture
def stub_main(monkeypatch):
    """``main`` with the gate passed and the phases stubbed out."""
    device = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
    monkeypatch.setattr(chip_smoke, "gate", lambda: (device, "/cache"))
    monkeypatch.setattr(
        chip_smoke, "census", lambda run: [{"kernel": "x", "status": "ok"}]
    )
    for leg in ("leg_a", "leg_b", "leg_c"):
        monkeypatch.setattr(chip_smoke, leg, lambda run, *a: {"leg": "x"})
    return device


def test_last_line_is_the_verdict_alone(stub_main, capsys):
    import json

    assert chip_smoke.main([]) == 0
    lines = capsys.readouterr().out.splitlines()
    assert json.loads(lines[-1]) == {"ok": True, "device": stub_main}
    assert lines[-2].startswith("summary ")
    assert json.loads(lines[-2][len("summary "):])["claim"] is None


def test_failed_phase_says_so_and_raises(stub_main, monkeypatch, capsys):
    import json

    def boom(run):
        raise chip_smoke.SmokeFailure("leg A: scrub found errors")

    monkeypatch.setattr(chip_smoke, "leg_a", boom)
    with pytest.raises(chip_smoke.SmokeFailure):
        chip_smoke.main([])
    lines = capsys.readouterr().out.splitlines()
    assert json.loads(lines[-1]) == {"ok": False, "device": stub_main}
    assert not any(line.startswith("summary ") for line in lines)
