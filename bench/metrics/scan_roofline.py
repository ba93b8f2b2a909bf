"""Share of its roofline the selective-scan kernel reached in the traced
stretch (%): the least time the chip could take for every Mamba layer's
scan of each traced chunk's prefill (the architecture's ``counts.scan``,
its flops over the peak or its operands and output over bandwidth,
whichever is larger; ``bench.counts``) over the kernel's device time
(``scan_us_per_token.scan_seconds``).  The chip's peaks give only the
MXU's flop rate, and the scan runs on the vector unit, so the bytes set
the least time.  A chunk split over a group of k devices counts k calls
of rows / k."""

from bench import counts
from bench.metrics.scan_us_per_token import scan_seconds


def read(run):
    if run.trace is None or not run.peak or not run.traced \
            or not hasattr(run.counts, "scan"):
        return None
    secs = scan_seconds(run.trace)
    if not secs:
        return None
    p = run.cell.traffic["prompt_len"]
    least = sum(k * counts.least_time(*run.counts.scan(rows / k, p),
                                      run.peak)[0]
                for rows, k in run.traced)
    return 100.0 * run.counts.mamba_layers * least / secs
