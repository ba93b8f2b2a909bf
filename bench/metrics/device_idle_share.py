"""Share of scheduler-step time in the traced stretch in which no
operation ran on the device (%), averaged over the devices.  Steps come
from the scheduler's history (end = last row done, start = end minus
``t_step``), placed on the trace's clock by the ``bench.clock`` mark."""

import numpy as np

from bench import trace


def read(run):
    tr = run.trace
    if tr is None or run.trace_mark_perf is None or not run.steps:
        return None

    def ns(t):
        return tr["t0_ns"] + (t - run.trace_mark_perf) * 1e9

    spans = []
    for s in run.steps:
        end = ns(float(np.nanmax(s["row_done_at"])))
        lo, hi = max(end - s["t_step"] * 1e9, tr["t0_ns"]), min(end, tr["t1_ns"])
        if hi > lo:
            spans.append((lo, hi))
    total = sum(hi - lo for lo, hi in spans)
    if not total or not tr["devices"]:
        return None
    idle = [total - sum(trace.overlap(trace.busy(d), lo, hi) for lo, hi in spans)
            for d in tr["devices"]]
    return 100.0 * float(np.mean(idle)) / total
