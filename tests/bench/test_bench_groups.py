"""A deployment of unequal device groups (3 + 1) loads from a
configuration file and serves through the harness, on four CPU devices:
the four-chip cell of the paper's layout needs only data.  Its metric of
the split, ``split_imbalance``, reads the scheduler's per-group step
times."""

from __future__ import annotations

import json
import os
import subprocess
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

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
imbalance = harness._module("metrics", "split_imbalance").read(run)
print(json.dumps({{"correct": line["correct"], "failed": line["failed"],
                  "rows": rows, "devices": len(jax.devices()),
                  "split_imbalance": imbalance}}))
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
    assert out["split_imbalance"] is not None
    assert 0.0 <= out["split_imbalance"] < 100.0


def _imbalance(steps):
    run = SimpleNamespace(steps=[{"t_group": t, "rows": r} for t, r in steps])
    return harness._module("metrics", "split_imbalance").read(run)


def test_split_imbalance_is_the_median_steps_spread_over_its_slowest():
    # (4 - 3) / 4, (2 - 2) / 2 and (5 - 1) / 5: median 25%
    assert _imbalance([([3.0, 4.0], [192, 64]), ([2.0, 2.0], [128, 128]),
                       ([1.0, 5.0], [64, 192])]) == pytest.approx(25.0)
    # three groups: the slowest and the fastest of them
    assert _imbalance([([1.0, 2.0, 4.0], [8, 8, 8])]) == pytest.approx(75.0)


def test_split_imbalance_counts_only_groups_given_rows():
    # a dropped group's 1e-9 s (no rows) is no group's time
    assert _imbalance([([1e-9, 2.0, 3.0], [0, 64, 64])]) == \
        pytest.approx(100 / 3)
    assert _imbalance([([1e-9, 2.0], [0, 128])]) is None
    assert _imbalance([([2.0], [128])]) is None
    assert _imbalance([]) is None
