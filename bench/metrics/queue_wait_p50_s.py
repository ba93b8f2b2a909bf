"""Median wait of the completed requests in admission and the batcher:
dispatch minus due arrival, from the program's request records."""

import numpy as np


def read(run):
    waits = [r["queue_delay_s"] for r in run.completed]
    return float(np.percentile(waits, 50)) if waits else None
