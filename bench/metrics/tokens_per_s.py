"""Output tokens of every completed request over the time from the first
due arrival to the last completion (host clock)."""


def read(run):
    done = run.completed
    if not done:
        return None
    first = min(r["t_arrival"] for r in run.records)
    last = max(r["t_done"] for r in done)
    tokens = sum(r["rows"] for r in done) * run.cell.traffic["gen"]
    return tokens / (last - first)
