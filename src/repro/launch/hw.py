"""Per-chip peak figures, keyed by ``jax.Device.device_kind``.

Source: Google Cloud documentation, "TPU v5e" — per chip 197 TFLOP/s
bf16, 393 TOP/s int8, 16 GB of HBM at 819 GB/s, 1,600 Gbit/s of
chip-to-chip interconnect (taken as 4 links of 50 GB/s; the pod roofline
conservatively charges one link per collective). JAX reports a v5e as
``"TPU v5 lite"``. A kind missing from the table is an error, never a
default: a roofline share against the wrong peaks is worse than none.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional


@dataclasses.dataclass(frozen=True)
class Peaks:
    flops_bf16: float       # FLOP/s
    hbm_bw: float           # bytes/s
    ici_bw_per_link: float  # bytes/s


V5E = Peaks(flops_bf16=197e12, hbm_bw=819e9, ici_bw_per_link=50e9)

PEAKS: Dict[str, Peaks] = {"TPU v5 lite": V5E}


def peaks(device_kind: str) -> Peaks:
    """Peaks of one device kind; KeyError for a kind not in the table."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no peak figures for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}") from None


def device_peaks(device: Optional[object] = None) -> Peaks:
    """Peaks of ``device`` (default: the first JAX device)."""
    if device is None:
        import jax
        device = jax.devices()[0]
    return peaks(device.device_kind)
