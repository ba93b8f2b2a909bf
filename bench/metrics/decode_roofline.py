"""Share of its roofline the decode program reached in the traced
stretch (%): for every decode step of every traced chunk, the least time
the chip could take (``bench.counts``: weights, plus the cache up to
``pos + 1``, over bandwidth, or flops over peak, whichever is larger),
over the decode program's device time."""

from bench import counts, trace


def read(run):
    if run.trace is None or not run.peak:
        return None
    secs, calls = trace.program(run.trace, trace.DECODE)
    if not calls or not run.traced:
        return None
    p, g = run.cell.traffic["prompt_len"], run.cell.traffic["gen"]
    least = sum(k * counts.least_time(*run.counts.decode(rows / k, p + i),
                                      run.peak)[0]
                for rows, k in run.traced for i in range(g - 1))
    return 100.0 * least / secs
