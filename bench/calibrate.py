#!/usr/bin/env python3
"""Readings that set a cell's correctness limits, on the chip.

    python3 bench/calibrate.py --workload qwen2.5-3b.chat --seeds 1,2,3 \
        --control-seeds 3 --seconds 10

In one process, for each seed: serve the cell's traffic at its own rate
for ``--seconds`` of arrivals, exactly as ``bench/run.py`` does, and read
the numbers ``correct`` compares on the sampled served rows (the lower
readings).  For the first ``--control-seeds`` seeds, also read the same
numbers from the control, the reference one precision down, over the same
prompts and served tokens (the upper readings).  A limit lies between the
largest lower reading and the smallest upper one.  One line per seed,
then a JSON summary last on standard output.
"""

from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        print(f"calibrate: no program under {ROOT / 'src'}", file=sys.stderr)
        return 2
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", str(ROOT / ".jax_cache"))
    from bench import harness
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    devices = jax.devices()
    cell = harness.Cell.resolve(args.workload)
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print("calibrate: needs a TPU with the cell's chips", file=sys.stderr)
        return 1
    ref = harness._module("reference", cell.config["reference"])
    lower, upper = {}, {}
    for k, seed in enumerate(int(s) for s in args.seeds.split(",")):
        run = harness.serve(cell, seed=seed, seconds=args.seconds,
                            devices=devices, t0=time.perf_counter())
        t = time.perf_counter()
        lower[seed] = {n: c["value"] for n, c in harness.check(run).items()}
        t_ref = time.perf_counter() - t
        line = {"seed": seed, "served": lower[seed], "reference_s": t_ref,
                "rows": len(run.sample["rows"]) if run.sample else 0,
                "completed": len(run.completed), "requests": run.n_requests}
        if k < args.control_seeds and run.sample is not None:
            s = run.sample
            upper[seed] = ref.readings(
                cell.config, seed, s["prompts"], s["tokens"], s["logits"],
                control=True, block_rows=cell.settings["check"]["rows"])
            line["control"] = upper[seed]
        print(json.dumps(line), flush=True)
    names = cell.settings["check"]["limits"]
    print(json.dumps({
        "workload": args.workload,
        "lower": {n: max(v[n] for v in lower.values()) for n in names},
        "upper": {n: min(v[n] for v in upper.values()) for n in names}
        if upper else None,
        "seeds": len(lower), "control_seeds": len(upper)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
