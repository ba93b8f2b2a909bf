"""Device time of one execution of the decode program (milliseconds),
averaged over the traced stretch.  Module rule: ``bench.trace.DECODE``."""

from bench import trace


def read(run):
    if run.trace is None:
        return None
    secs, calls = trace.program(run.trace, trace.DECODE)
    return secs / calls * 1e3 if calls else None
