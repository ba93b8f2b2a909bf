"""How unevenly the scheduler's split loaded the device groups (%): for
each scheduler step of the window, (slowest group's time - fastest
group's time) / slowest group's time, from the step's per-group times
(``t_group``: each group timed from its own first dispatch to its last
chunk's completion, host clock); the median over the steps.  Only groups
given rows in a step count, so a deployment of one group, or a step that
loaded one group, has nothing to read."""

import numpy as np


def read(run):
    shares = []
    for step in run.steps:
        times = [t for t, rows in zip(step["t_group"], step["rows"]) if rows]
        if len(times) >= 2:
            shares.append((max(times) - min(times)) / max(times))
    return 100.0 * float(np.median(shares)) if shares else None
