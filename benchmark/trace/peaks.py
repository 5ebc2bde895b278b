"""Published per-chip peaks, keyed by ``jax.devices()[0].device_kind``.
A device that is not here is an error, never a default. Copied from
``bench.py`` (``PUBLISHED_PEAKS``; PERF.md, Open questions, lists the
original for a later PR to delete)."""

from __future__ import annotations

PUBLISHED_PEAKS = {
    "TPU v5 lite": {
        "hbm_bytes_per_s": 819.0e9,
        "int8_ops_per_s": 393.0e12,
        "bf16_flops_per_s": 197.0e12,
        "source": "Google Cloud documentation, \"TPU v5e\" (system "
                  "architecture table: per-chip HBM bandwidth and peak "
                  "compute)",
    },
}


def published_peaks(device_kind: str) -> dict:
    peaks = PUBLISHED_PEAKS.get(device_kind)
    if peaks is None:
        raise RuntimeError(
            f"no published peaks for device_kind {device_kind!r} "
            f"(know {sorted(PUBLISHED_PEAKS)}); add its row with a source"
        )
    return peaks
