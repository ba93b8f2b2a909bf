"""The least time a chip could take for a call, from the operations and
needed bytes that the configuration's architecture module counts
(``counts(config)`` in ``bench/models/<reference>.py``) and the chip's
peaks (``bench/peaks.json``).  A roofline share is that time over the
call's device time."""

from __future__ import annotations


def least_time(flops: float, nbytes: float, peak: dict) -> tuple[float, str]:
    """The least time the chip could take, and which bound sets it."""
    t_flops = flops / peak["bf16_flops_per_s"]
    t_bytes = nbytes / peak["hbm_bytes_per_s"]
    return (t_flops, "compute") if t_flops >= t_bytes else (t_bytes, "memory")
