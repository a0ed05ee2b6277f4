"""Published per-chip peaks, keyed by ``jax.Device.device_kind``.

Copied from ``repro.roofline.analysis.PEAKS`` so that the yardstick stays
fixed.  Source: Google Cloud documentation, "TPU v5e" (system
architecture): 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB of HBM at
819 GB/s.  A float32 product at the highest precision takes several bf16
passes, so the bf16 peak is an upper bound on its rate and a roofline
drawn with it is a lower bound on the time.
"""
from __future__ import annotations

from typing import Dict

PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {"peak_flops": 197e12, "peak_int8_ops": 393e12,
                    "hbm_bw": 819e9, "hbm_bytes": 16e9},
}


def peaks(device_kind: str) -> Dict[str, float]:
    """Peaks of one chip of ``device_kind``; an unknown kind is an error."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no published peaks for device kind "
                         f"{device_kind!r}: add them to chipbench/peaks.py "
                         f"with their source") from None
