"""Published peaks of the chips the benchmark runs on, keyed by device_kind.

TPU v5e ("TPU v5 lite" in ``jax.devices()[0].device_kind``): 197 TFLOP/s
bf16 and 819 GB/s of HBM bandwidth (Google Cloud documentation, "TPU
v5e"). No float32 compute peak is published for the v5e, so a float32
kernel's roofline is taken by its bytes alone.
"""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Peaks:
    """One chip's published peaks."""

    bf16_flops: float  # FLOP/s
    hbm_bytes: float  # bytes/s
    source: str


PEAKS = {
    "TPU v5 lite": Peaks(
        bf16_flops=197e12, hbm_bytes=819e9,
        source='Google Cloud documentation, "TPU v5e"',
    ),
}


def peaks_for(device_kind: str) -> Peaks:
    """The peaks of ``device_kind``; a kind missing from the table is an error."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device_kind {device_kind!r}; add it to "
            f"chipbench/peaks.py (known: {sorted(PEAKS)})"
        ) from None


def roofline_share(flops: float | None, nbytes: float, seconds: float,
                   peaks: Peaks) -> float | None:
    """Least time the chip could take over the measured time, in percent.

    The least time is the larger of bytes over HBM bandwidth and, where a
    compute peak applies (``flops`` not None: bf16 work), FLOPs over the
    bf16 peak. None when nothing was measured.
    """
    if seconds <= 0:
        return None
    least = nbytes / peaks.hbm_bytes
    if flops is not None:
        least = max(least, flops / peaks.bf16_flops)
    return 100.0 * least / seconds
