"""Median latency of the completed requests, from each request's due
arrival to the completion of its last row (host clock)."""

import numpy as np


def read(run):
    lat = [r["latency_s"] for r in run.completed]
    return float(np.percentile(lat, 50)) if lat else None
