"""A deployment of unequal device groups (3 + 1) loads from a
configuration file and serves through the harness, on four CPU devices:
the four-chip cell of the paper's layout needs only data."""

from __future__ import annotations

import json
import os
import subprocess
import sys
from pathlib import Path

from bench import harness

SCRIPT = """
import json, sys, time
sys.path[:0] = {paths!r}
import jax
from bench import harness
from small_cell import small_cell
cell = small_cell(groups=[[0, 1, 2], [3]], row_quantum=1, chips=4)
line = harness.run_cell(cell, seed=4_000_000_003, seconds=1.5, traced=False,
                        devices=jax.devices(), t0=time.perf_counter())
run = line.pop("_run")
rows = [sum(s["rows"][g] for s in run.steps) for g in range(2)]
print(json.dumps({{"correct": line["correct"], "failed": line["failed"],
                  "rows": rows, "devices": len(jax.devices())}}))
"""


def test_three_plus_one_groups_serve_from_data():
    here = Path(__file__).resolve().parent
    paths = [str(harness.ROOT / "src"), str(harness.ROOT), str(here)]
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=4"}
    proc = subprocess.run([sys.executable, "-c", SCRIPT.format(paths=paths)],
                          env=env, capture_output=True, text=True,
                          timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["devices"] == 4
    assert out["correct"] and out["failed"] == 0
    assert all(r > 0 for r in out["rows"]), out
