"""A kernel's share of its roofline on the chip (``bench/peaks.json``).

The least time the chip could take for a piece of work is the larger of
its FLOPs over the peak FLOP/s and its bytes over the peak HBM bytes/s;
the share is that time over the time the work took.  Every
``<kernel>_roofline`` reader computes its share here, from the FLOPs and
bytes that its configuration's functions count and the device seconds
that the trace gives.
"""

from __future__ import annotations

from typing import Optional


def least_s(flops: float, nbytes: float, peak: dict) -> float:
    """Seconds the chip needs at least: FLOPs or bytes, whichever bound."""
    return max(flops / peak["bf16_flop_per_s"],
               nbytes / peak["hbm_bytes_per_s"])


def share(seconds: float, flops: float, nbytes: float,
          peak: Optional[dict]) -> Optional[float]:
    """``least_s`` over ``seconds``, in %; None where nothing ran."""
    if not seconds or seconds <= 0:
        return None
    if peak is None:
        raise ValueError("the device is not in bench/peaks.json")
    return least_s(flops, nbytes, peak) / seconds * 100
