"""Median wait of the completed requests before the engine admitted
them: first admission minus due arrival (``admit_lag_s`` of the
program's request records).  The engine takes arrivals only between
scheduler steps, so this is mostly the wait behind the step in flight.
A program whose records carry no ``admit_lag_s`` reads nothing."""

import numpy as np


def read(run):
    done = run.completed
    if not done or any(r.get("admit_lag_s") is None for r in done):
        return None
    return float(np.percentile([r["admit_lag_s"] for r in done], 50))
