"""95th percentile of the latency of the completed requests, due arrival
to the completion of the last row (host clock)."""

import numpy as np


def read(run):
    lat = [r["latency_s"] for r in run.completed]
    return float(np.percentile(lat, 95)) if lat else None
