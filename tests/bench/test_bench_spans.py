"""The readers of the program's own spans and request counters.

``host_gap_ms_per_step`` and ``decode_dispatch_us_per_step`` read the
host spans of a trace in ``bench.trace.compact``'s form;
``admit_lag_p50_s`` and ``batch_wait_p50_s`` read the request records.
Each is checked on a synthetic trace or records whose answer is worked
out by hand, finds nothing where the program records nothing (as a
program without the spans and counters does), and reads the spans of a
traced run of the harness on the CPU.
"""

from __future__ import annotations

import time
from types import SimpleNamespace

import numpy as np
import pytest

import jax

from bench import harness
from small_cell import small_cell

MS = 1_000_000


def read(name, run):
    return harness._module("metrics", name).read(run)


def traced(host, ops, gen=5):
    """A run whose traced stretch is 0-10 ms on one device."""
    tr = {"t0_ns": 0, "t1_ns": 10 * MS, "host": host,
          "devices": [{"name": "/device:TPU:0", "modules": [], "ops": ops}]}
    return SimpleNamespace(trace=tr, cell=SimpleNamespace(
        traffic={"prompt_len": 32, "gen": gen}))


# the device busy 1-4 and 6-9 ms; two engine steps, the engine idle
# between them 4.5-5.5 ms; a step already running at 0 ms has no span
OPS = [["a", 0, MS], ["b", 1 * MS, 3 * MS], ["c", 6 * MS, 3 * MS]]
STEPS = [["serve.step", 1 * MS, 3_500_000], ["serve.step", 5_500_000, 4 * MS]]
WAIT = [["serve.wait", 4_500_000, MS]]


def test_host_gap_leaves_out_idle_time_under_serve_wait():
    # idle from the first step's start to the last's end (1-9.5 ms):
    # 4-4.5, 5.5-6 and 9-9.5 ms over 2 steps
    assert read("host_gap_ms_per_step",
                traced(STEPS + WAIT, OPS)) == pytest.approx(0.75)
    # without the wait its millisecond counts as host gap
    assert read("host_gap_ms_per_step",
                traced(STEPS, OPS)) == pytest.approx(1.25)


def test_host_gap_ends_with_the_last_step():
    # the device idles from 9 ms to the stretch's end at 10 ms; only the
    # half millisecond before the last step ends is a step's gap, however
    # long the engine's exit and the harness's close take after it
    run = traced(STEPS + WAIT, OPS)
    run.trace["t1_ns"] = 60 * MS
    assert read("host_gap_ms_per_step", run) == pytest.approx(0.75)
    # a step that ends later owns the idle time up to its end, 10.5 ms
    late = [["serve.step", 1 * MS, 3_500_000], ["serve.step", 5_500_000, 5 * MS]]
    assert read("host_gap_ms_per_step",
                traced(late + WAIT, OPS)) == pytest.approx(1.25)


def test_host_gap_averages_the_devices():
    run = traced(STEPS + WAIT, OPS)
    run.trace["devices"].append({"name": "/device:TPU:1", "modules": [],
                                 "ops": [["d", 1 * MS, 9 * MS]]})
    # the second device idles nowhere after the first step starts
    assert read("host_gap_ms_per_step", run) == pytest.approx(0.375)


def test_decode_dispatch_divides_by_gen_minus_one_per_span():
    host = [["step.decode", 1 * MS, 300_000], ["step.decode", 5 * MS, 500_000],
            ["step.prefill", 0, 900_000]]
    # 800 us over 2 spans of 4 decode steps each
    assert read("decode_dispatch_us_per_step",
                traced(host, OPS, gen=5)) == pytest.approx(100.0)
    assert read("decode_dispatch_us_per_step",
                traced(host, OPS, gen=9)) == pytest.approx(50.0)


PREPARE = "CommonPjRtLoadedExecutable::ExecutePrepare"
ALLOCATE = "AllocateOutputBuffersWithInputReuse"


def test_decode_dispatch_leaves_out_the_runtime_waits():
    # a 2 ms decode loop of gen - 1 = 4 steps: one dispatch held 1 ms in
    # ExecutePrepare, of which 0.1 ms allocated its outputs (host work),
    # and a 0.3 ms AllocateBufferAwait; a wait outside the span is not
    # the loop's
    host = [["step.decode", 1 * MS, 2 * MS],
            [PREPARE, 1_200_000, 1 * MS],
            [ALLOCATE, 2_100_000, 100_000],
            ["AllocateBufferAwait", 2_500_000, 300_000],
            [PREPARE, 4 * MS, 1 * MS]]
    # 2 ms - 0.9 ms - 0.3 ms of the host's own, over 4 steps
    assert read("decode_dispatch_us_per_step",
                traced(host, OPS, gen=5)) == pytest.approx(200.0)
    # a wait nested in another is counted once
    nested = host + [["AllocateBufferAwait", 1_300_000, 200_000]]
    assert read("decode_dispatch_us_per_step",
                traced(nested, OPS, gen=5)) == pytest.approx(200.0)


@pytest.mark.parametrize("name", ["host_gap_ms_per_step",
                                  "decode_dispatch_us_per_step"])
def test_span_readers_find_nothing_without_their_spans(name):
    assert read(name, SimpleNamespace(trace=None)) is None
    # a program that records no spans: only the harness's own marks
    bare = [["bench.clock", 0, 1000], ["bench.chunk", 10, 5 * MS]]
    assert read(name, traced(bare, OPS)) is None


def records(rows):
    """Completed requests from (queue_delay_s, admit_lag_s) pairs."""
    recs = [{"status": "completed", "queue_delay_s": q, "admit_lag_s": a}
            for q, a in rows]
    return SimpleNamespace(completed=recs)


def test_admit_lag_and_batch_wait_split_the_queue_wait():
    run = records([(1.5, 1.49), (0.2, 0.0), (3.0, 2.9), (1.0, 0.98),
                   (2.0, 1.5)])
    assert read("admit_lag_p50_s", run) == pytest.approx(1.49)
    # waits 0.01, 0.2, 0.1, 0.02, 0.5: medians split request by request,
    # so the parts' medians need not add up to the whole's
    assert read("batch_wait_p50_s", run) == pytest.approx(0.1)
    assert read("queue_wait_p50_s", run) == pytest.approx(1.5)


def test_record_readers_find_nothing_without_admission_times():
    # records as a program without the counter writes them
    old = SimpleNamespace(completed=[{"status": "completed",
                                      "queue_delay_s": 1.0}])
    for name in ("admit_lag_p50_s", "batch_wait_p50_s"):
        assert read(name, old) is None
        assert read(name, SimpleNamespace(completed=[])) is None


def test_a_traced_run_reads_the_program_spans():
    line = harness.run_cell(small_cell(), seed=3_000_000_019, seconds=1.5,
                            traced=True, devices=jax.devices()[:1],
                            t0=time.perf_counter())
    run, metrics = line["_run"], line["metrics"]
    assert line["correct"], line["checks"]
    names = {h[0] for h in run.trace["host"]}
    assert {"serve.step", "sched.dispatch", "step.decode"} <= names
    for name in ("admit_lag_p50_s", "batch_wait_p50_s",
                 "decode_dispatch_us_per_step"):
        assert metrics[name]["value"] >= 0, name
    # the CPU has no device plane for the host gap to be read against
    assert "host_gap_ms_per_step" not in metrics
    for r in run.completed:
        wait = r["queue_delay_s"] - r["admit_lag_s"]
        assert r["admit_lag_s"] >= 0 and wait >= 0
    lags = [r["admit_lag_s"] for r in run.completed]
    assert metrics["admit_lag_p50_s"]["value"] == float(np.median(lags))
