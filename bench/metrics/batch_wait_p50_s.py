"""Median wait of the completed requests between admission and dispatch:
``queue_delay_s - admit_lag_s`` of the program's request records, the
time spent queued in the batcher.  With ``admit_lag_p50_s`` it splits
``queue_wait_p50_s`` request by request.  A program whose records carry
no ``admit_lag_s`` reads nothing."""

import numpy as np


def read(run):
    done = run.completed
    if not done or any(r.get("admit_lag_s") is None for r in done):
        return None
    return float(np.percentile([r["queue_delay_s"] - r["admit_lag_s"]
                                for r in done], 50))
