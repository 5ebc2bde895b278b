"""Single-parity XOR codec — the ``xor`` plugin.

RAID-4/5-class protection: one parity chunk equal to the XOR of the k
data chunks (generator parity row all ones over GF(2^8); trivially
MDS for m=1 since every column is nonzero). The reference carries no
standalone xor plugin — its XOR codes live inside jerasure's
bit-matrix techniques — but Azure-LRC-style locally repairable codes
pair GF global parities with *XOR local parities*, and that is this
plugin's job here: ``codecs/lrc.py`` uses it for generated local
layers under ``local_parity=xor``, so local-group repair rows are
0/1-valued and ride the schedule-native XOR engine (the round-11
``as_01_matrix`` w=1 route: encode, decode, AND parity-delta all
dispatch as pure XOR programs with ``sched_*`` counter visibility)
instead of streaming a bit-plane matrix through the MXU.

Usable standalone too (``plugin=xor``, profile ``k=<n>``): the
cheapest single-fault pool config there is.
"""

from __future__ import annotations

import numpy as np

from ceph_tpu import PLUGIN_ABI_VERSION

from .base import to_int
from .interface import ErasureCodeProfile
from .matrix_codec import MatrixErasureCodec
from .registry import registry


class XorCodec(MatrixErasureCodec):
    """k data chunks + 1 XOR parity, on the shared byte-matrix
    dispatch engine (host GF tables for small ops; the schedule
    engine's w=1 route on TPU — the all-ones row IS a one-line XOR
    schedule; MXU/einsum otherwise)."""

    DEFAULT_K = 2

    def init(self, profile: ErasureCodeProfile) -> None:
        self.profile = dict(profile)
        self.k = to_int("k", profile, self.DEFAULT_K)
        self.m = to_int("m", profile, 1)
        if self.k < 1:
            raise ValueError(f"k={self.k} must be >= 1")
        if self.m != 1:
            raise ValueError("xor plugin supports m=1 only")
        g = np.vstack(
            [np.eye(self.k, dtype=np.uint8),
             np.ones((1, self.k), dtype=np.uint8)]
        )
        self._set_generator(g)


registry.register("xor", XorCodec, PLUGIN_ABI_VERSION)
