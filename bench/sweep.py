#!/usr/bin/env python3
"""Find a cell's knee once: serve its traffic at several offered rates.

    python3 bench/sweep.py --workload qwen2.5-3b.chat --rates 3,4,5,6 --seconds 30

One process, the chip's only user: for each rate the cell's traffic is
served as ``bench/run.py`` serves it (same configuration, deployment and
mix; the check against the reference is skipped).  A rate is sustained
when no request is shed and the backlog does not grow across the window:
the median queue wait of the last third of the requests exceeds that of
the first third by less than the longest scheduler step.  The knee is
the highest sustained rate; a cell offers about 0.8 of it.  One line per
rate, then a JSON summary as the last line of standard output.
"""

from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from dataclasses import replace  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def sweep_point(run) -> dict:
    import numpy as np
    done = sorted(run.completed, key=lambda r: r["t_arrival"])
    third = max(len(done) // 3, 1)
    waits = [r["queue_delay_s"] for r in done]
    lat = [r["latency_s"] for r in done]
    longest = max((s["t_step"] for s in run.steps), default=0.0)
    growth = float(np.median(waits[-third:]) - np.median(waits[:third])) \
        if done else float("inf")
    shed = run.n_requests - len(done)
    span = max(r["t_done"] for r in done) - min(r["t_arrival"] for r in run.records) \
        if done else float("nan")
    return {"requests": run.n_requests, "completed": len(done), "shed": shed,
            "tokens_per_s": sum(r["rows"] for r in done)
            * run.cell.traffic["gen"] / span,
            "e2e_p50_s": float(np.percentile(lat, 50)) if lat else None,
            "e2e_p95_s": float(np.percentile(lat, 95)) if lat else None,
            "queue_wait_growth_s": growth, "longest_step_s": longest,
            "steps": len(run.steps),
            "sustained": shed == 0 and growth < longest}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True,
                    help="comma-separated offered rates, requests/s")
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        print(f"sweep: no program under {ROOT / 'src'}", file=sys.stderr)
        return 2
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", str(ROOT / ".jax_cache"))
    from bench import harness
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    devices = jax.devices()
    cell = harness.Cell.resolve(args.workload)
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print("sweep: needs a TPU with the cell's chips", file=sys.stderr)
        return 1
    points = {}
    for rate in (float(r) for r in args.rates.split(",")):
        at = replace(cell, settings={**cell.settings, "rate_rps": rate})
        run = harness.serve(at, seed=args.seed, seconds=args.seconds,
                            devices=devices, t0=time.perf_counter())
        points[rate] = sweep_point(run)
        print(f"rate {rate}: {json.dumps(points[rate])}", flush=True)
    sustained = [r for r, p in points.items() if p["sustained"]]
    knee = max(sustained, default=None)
    print(json.dumps({"workload": args.workload, "seconds": args.seconds,
                      "knee_rps": knee, "points": points}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
