"""The one table of chip peaks, keyed by ``jax.devices()[0].device_kind``.

Copied from ``bench.py:_CHIP_PEAKS`` (the original is listed in PERF.md for
a later PR to delete).  A kind that is not in the table is an error, never
a default: a share computed against the wrong peak is worse than none.
"""
from __future__ import annotations

#: Google Cloud documentation, "TPU v5e" (System architecture): 197 TFLOP/s
#: bf16 and 819 GB/s of HBM bandwidth per chip, 16 GB of HBM.
PEAKS = {
    "TPU v5 lite": {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9,
                    "source": "cloud.google.com/tpu/docs/v5e"},
}


def peaks_for(device_kind: str) -> dict:
    """The peaks of ``device_kind``; ``KeyError`` with the known kinds
    when the table does not hold it."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"device kind {device_kind!r} is not in the peaks "
                       f"table (known: {sorted(PEAKS)})") from None


def share_pct(achieved: float, peak: float, what: str) -> float:
    """``achieved / peak`` in percent.  A share above 100% means the
    operations or bytes were counted too high or the time leaves out part
    of the work: that is a fault of the yardstick, raised and never
    clipped."""
    pct = 100.0 * achieved / peak
    if pct > 100.0:
        raise ValueError(f"{what}: {pct:.2f}% of peak is above 100% "
                         f"(achieved {achieved:.4g}, peak {peak:.4g})")
    return pct
