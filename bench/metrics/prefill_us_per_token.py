"""Device time of the prefill program per prompt token it ran
(microseconds), over the chunks of the traced stretch.  Padding rows
count: the device runs them.  Module rule: ``bench.trace.PREFILL``."""

from bench import trace


def read(run):
    if run.trace is None:
        return None
    secs, calls = trace.program(run.trace, trace.PREFILL)
    tokens = sum(rows for rows, _ in run.traced) * run.cell.traffic["prompt_len"]
    if not calls or not tokens:
        return None
    return secs / tokens * 1e6
