"""The offered load of a traffic mix, as the benchmark defines it.

The program's ``serve_requests`` builds its arrival source itself
(``serve.RequestSource``), from ``n_requests``, ``rate_rps`` and a seed,
so the benchmark cannot hand it a schedule.  It asks for ``rate_rps *
seconds`` requests and, once they are served, checks from the served
records that the program offered the mix: every request, each of the
mix's shape, with rows the mix allows and a deadline of one of its
classes, arriving at the mix's rate.  A program whose source drifts from
the mix fails loudly instead of measuring other traffic.
"""

from __future__ import annotations

import math


def n_requests(rate_rps: float, seconds: float) -> int:
    return max(1, int(round(rate_rps * seconds)))


def classes(traffic: dict):
    """The mix's request classes as the program's ``RequestClass``."""
    from repro.serve import RequestClass
    return tuple(RequestClass(c["name"], slo_s=c["slo_s"],
                              priority=c["priority"], weight=c["weight"])
                 for c in traffic["classes"])


def departures(traffic: dict, rate_rps: float, n: int, records: list[dict],
               opened: float) -> list[str]:
    """How the served ``records`` depart from ``n`` requests of the mix
    offered at ``rate_rps`` from the instant ``opened``; empty when they
    do not.  The rate is held to five standard errors of a Poisson
    process's: the n-th arrival of one lies ``n / rate`` after its start,
    give or take ``sqrt(n) / rate``."""
    out = []
    if sorted(r["rid"] for r in records) != list(range(n)):
        out.append(f"{len(records)} requests served of {n} offered")
    shape = [traffic["prompt_len"], traffic["gen"]]
    if any(list(r["shape"]) != shape for r in records):
        out.append(f"a request not of the mix's shape {shape}")
    if any(r["rows"] not in traffic["rows_choices"] for r in records):
        out.append(f"a request's rows outside {traffic['rows_choices']}")
    names = {c["name"] for c in traffic["classes"]}
    if any(r["klass"] not in names for r in records):
        out.append(f"a request of a class outside {sorted(names)}")
    if records:
        span = max(r["t_arrival"] for r in records) - opened
        if abs(span - n / rate_rps) > 5 * math.sqrt(n) / rate_rps:
            out.append(f"{n} arrivals over {span:.3f} s, not {rate_rps} "
                       "requests/s")
    return out
