#!/usr/bin/env python3
"""Run one cell of ``BENCHMARK.json`` on the chips of this machine.

    python3 bench/run.py --workload qwen2.5-3b.chat --seed 7 --seconds 30 --trace 0

Serves the cell's traffic through the program's ``serve_requests`` for
``--seconds`` of arrivals, checks a sample of what was served against the
cell's plain reference, and prints one JSON line last on standard output:
``correct``, ``attempted``, ``failed``, ``metrics`` (the end-to-end
metrics with ``--trace 0``, the per-layer ones with ``--trace 1``),
``device``, with ``--trace 1`` a ``breakdown``, and last ``checks``, each
number compared beside its limit.  Those numbers are also the last lines
of standard error.

It exits nonzero and prints no result when the program is not beside it,
when JAX finds no TPU or fewer chips than the cell asks for, or when the
cell's files are missing or disagree with the program.  JAX's compilation
cache is ``$JAX_COMPILATION_CACHE_DIR`` when set, else ``.jax_cache`` at
the root of the checkout.
"""

from __future__ import annotations

import time

T0 = time.perf_counter()        # set-up is timed from here

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    if not (ROOT / "src" / "repro").is_dir():
        print(f"bench: no program under {ROOT / 'src'}", file=sys.stderr)
        return 2
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", str(ROOT / ".jax_cache"))
    from bench import harness

    try:
        cell = harness.Cell.resolve(args.workload)
    except harness.BenchError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2

    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"bench: JAX found no TPU (platform {devices[0].platform})",
              file=sys.stderr)
        return 1
    if len(devices) < cell.chips:
        print(f"bench: {args.workload} needs {cell.chips} chips, JAX found "
              f"{len(devices)}", file=sys.stderr)
        return 1
    try:
        line = harness.run_cell(cell, seed=args.seed, seconds=args.seconds,
                                traced=bool(args.trace), devices=devices,
                                t0=T0)
    except harness.BenchError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    harness.emit(line)
    return 0


if __name__ == "__main__":
    sys.exit(main())
