"""Host time to dispatch one decode step (microseconds): the program's
``step.decode`` spans in the traced stretch, each the host loop of
``gen - 1`` decode and argmax dispatches of one chunk, less the time the
runtime held that loop back, over ``(gen - 1)`` times the number of
spans.

The loop runs ahead of the device until the runtime holds a dispatch in
``ExecutePrepare`` while earlier work finishes (about a device step
each time), or a buffer in ``AllocateBufferAwait``; that wait is the
device's time, not the host's.  The wait counted is ``ExecutePrepare``
outside its own allocation of output buffers, which is host work,
together with ``AllocateBufferAwait``.  The compact trace keeps no
threads, so in a cell whose device groups dispatch at once each group's
span would lose the others' waits too.  The host events kept by
``bench.trace.compact`` last 20 us or longer: a shorter wait counts as
host time.  A program that records no ``step.decode`` span reads nothing."""

from bench import trace

PREPARE = "CommonPjRtLoadedExecutable::ExecutePrepare"
ALLOCATE = "AllocateOutputBuffersWithInputReuse"
AWAIT = "AllocateBufferAwait"


def _minus(spans, holes):
    """The parts of the sorted, merged ``(start, end)`` ``spans`` that no
    interval of the sorted, merged ``holes`` covers."""
    out = []
    for s, e in spans:
        for hs, he in holes:
            if he <= s or hs >= e:
                continue
            if hs > s:
                out.append((s, hs))
            s = max(s, he)
        if s < e:
            out.append((s, e))
    return out


def read(run):
    if run.trace is None:
        return None
    host = run.trace["host"]
    spans = [h for h in host if h[0] == "step.decode"]
    steps = run.cell.traffic["gen"] - 1
    if not spans or steps < 1:
        return None
    own = 0
    for _, lo, dur in spans:
        hi = lo + dur
        inside = [h for h in host if lo <= h[1] < hi]

        def merged(name):
            return trace.union((h[1], h[2]) for h in inside if h[0] == name)

        held = _minus(merged(PREPARE), merged(ALLOCATE))
        waits = trace.union([(s, e - s) for s, e in held]
                            + [(h[1], h[2]) for h in inside if h[0] == AWAIT])
        own += dur - trace.overlap(waits, lo, hi)
    return own / (steps * len(spans)) / 1e3
