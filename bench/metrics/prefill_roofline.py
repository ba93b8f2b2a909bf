"""Share of its roofline the prefill program reached in the traced
stretch (%): the least time the chip could take for each traced chunk's
prefill (``bench.counts``, flops over peak or needed bytes over
bandwidth, whichever is larger) over the prefill's device time.  A chunk
split over a group of k devices counts k calls of rows / k."""

from bench import counts, trace


def read(run):
    if run.trace is None or not run.peak:
        return None
    secs, calls = trace.program(run.trace, trace.PREFILL)
    if not calls or not run.traced:
        return None
    p = run.cell.traffic["prompt_len"]
    least = sum(k * counts.least_time(*run.counts.prefill(rows / k, p),
                                      run.peak)[0]
                for rows, k in run.traced)
    return 100.0 * least / secs
