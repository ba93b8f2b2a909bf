"""Device idle time per engine step that the serving loop's host work
leaves (milliseconds): the time in which no operation ran on the device
and the engine was not waiting for work (no ``serve.wait`` span), from
the start of the first ``serve.step`` span of the traced stretch to the
end of the last, averaged over the devices, over the number of
``serve.step`` spans.  A step already running when the profiler opened
has no span, so the stretch starts at the first step that has one, and
it ends with the last step, so the engine's exit and the harness's
close after it are no step's gap.  The host events kept by
``bench.trace.compact`` last 20 us or longer: a shorter ``serve.wait``
is lost and its idle time counts as host gap.  A program that records
no ``serve.step`` span reads nothing."""

from bench import trace


def read(run):
    tr = run.trace
    if tr is None or not tr["devices"]:
        return None
    steps = [h for h in tr["host"] if h[0] == "serve.step"]
    if not steps:
        return None
    lo = min(h[1] for h in steps)
    hi = max(h[1] + h[2] for h in steps)
    waits = [(h[1], h[2]) for h in tr["host"] if h[0] == "serve.wait"]
    idle = []
    for d in tr["devices"]:
        covered = trace.union([(s, e - s) for s, e in trace.busy(d)] + waits)
        idle.append((hi - lo) - trace.overlap(covered, lo, hi))
    return sum(idle) / len(idle) / len(steps) / 1e6
