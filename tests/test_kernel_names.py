"""The names the benchmark finds the codec kernels by.

A cell of the benchmark names its dominant codec kernel by a piece of
the device event's text (``benchmark/workloads/<cell>.json``,
``codec_kernel.match``): ``%_apply_tiled_csum`` for the fused
encode+csum Pallas call, ``%_apply_tiled.`` for the plain one (decode),
``jit_local`` for the mesh's ring encode program. The TPU compiler
names a Pallas custom call ``%<kernel_name>.<n>`` and a program
``jit_<function>``; both used to follow Python function names, so a
rename would have emptied ``codec_roofline`` without a failing test.
They are pinned with explicit names now; this lowers each for the TPU
on the CPU (no chip, no compile) and looks for every cell's string.
"""

import glob
import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ceph_tpu.gf import gf_matrix_to_bitmatrix
from ceph_tpu.ops import pallas_encode as pe
from ceph_tpu.parallel import collectives
from ceph_tpu.parallel.mesh import make_ec_mesh

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELLS = sorted(glob.glob(os.path.join(ROOT, "benchmark", "workloads", "*.json")))
K, M, CHUNK = 8, 4, 4096


def _bitmatrix(rows: int) -> np.ndarray:
    rng = np.random.default_rng(5)
    return gf_matrix_to_bitmatrix(
        rng.integers(1, 255, (rows, K)).astype(np.uint8)
    )


def _lowered_for_tpu(fn, *specs) -> str:
    return jax.jit(fn).trace(*specs).lower(
        lowering_platforms=("tpu",)
    ).as_text()


def _device_event_names() -> list[str]:
    """What a trace of the three codec routes would show, as far as the
    program decides it: ``%<kernel_name>.1`` per Pallas call, the module
    name of the mesh program."""
    data = jax.ShapeDtypeStruct((16, K, CHUNK), jnp.uint8)
    names = []
    for text in (
        _lowered_for_tpu(
            lambda x: pe.gf_encode_csum_bitplane_pallas(
                _bitmatrix(M), x, CHUNK, interpret=False
            ), data,
        ),
        _lowered_for_tpu(
            lambda x: pe.gf_encode_bitplane_pallas(
                _bitmatrix(1), x, interpret=False
            ), data,
        ),
    ):
        kernels = re.findall(r'kernel_name = "([^"]+)"', text)
        assert len(kernels) == 1, kernels
        names.append(f"%{kernels[0]}.1 = custom-call(...)")
    mesh = make_ec_mesh(4, k=K)
    ring = collectives._ring_parity_fn(mesh, CHUNK)
    text = ring.lower(
        jax.ShapeDtypeStruct((M * 8, K * 8), jnp.uint8), data
    ).as_text()
    (module,) = re.findall(r"^module @(\S+)", text, flags=re.M)
    names.append(f"{module}(1234567)")
    return names


@pytest.fixture(scope="module")
def event_names():
    return _device_event_names()


def test_the_names_are_the_pinned_constants(event_names):
    assert event_names[0].startswith(f"%{pe.FUSED_KERNEL_NAME}.1 ")
    assert event_names[1].startswith(f"%{pe.APPLY_KERNEL_NAME}.1 ")
    assert event_names[2].startswith(
        f"jit_{collectives.RING_PROGRAM_NAME}("
    )


@pytest.mark.parametrize(
    "path", CELLS, ids=[os.path.basename(p)[:-5] for p in CELLS]
)
def test_each_cells_match_string_finds_exactly_its_kernel(path, event_names):
    with open(path, encoding="utf-8") as f:
        kernel = json.load(f)["codec_kernel"]
    hits = [n for n in event_names if kernel["match"] in n]
    assert len(hits) == 1, (kernel["match"], event_names)
    want = {
        "%_apply_tiled_csum": 0, "%_apply_tiled.": 1, "jit_local": 2,
    }[kernel["match"]]
    assert hits[0] is event_names[want]


def test_there_is_a_cell_to_check():
    assert len(CELLS) >= 3
