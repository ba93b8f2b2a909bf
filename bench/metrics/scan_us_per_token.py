"""Device time of the selective-scan kernel per prompt token in the
traced stretch's prefills (microseconds), summed over the Mamba layers.
Padding rows count: the device runs them.  The kernel is found by its
name, ``mamba_scan`` (``SCAN``, read off a chip trace by hand: the
Pallas call's HLO op ``%mamba_scan.N``, a ``custom-call``); a program
that runs no such kernel reads nothing."""

import re

SCAN = re.compile(r"^%mamba_scan(\.\d+)?\s")


def scan_seconds(tr) -> float:
    """Device seconds of the scan kernel, summed over every device."""
    return sum(e[2] for d in tr["devices"] for e in d["ops"]
               if SCAN.match(e[0])) / 1e9


def read(run):
    if run.trace is None:
        return None
    secs = scan_seconds(run.trace)
    tokens = sum(rows for rows, _ in run.traced) * run.cell.traffic["prompt_len"]
    if not secs or not tokens:
        return None
    return secs / tokens * 1e6
