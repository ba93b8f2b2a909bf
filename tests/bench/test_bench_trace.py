"""The reduction from a trace to metrics, on a trace recorded on the chip.

``data/trace_qwen_chat_v5e.json.gz`` is a slice of a traced run of
``qwen2.5-3b.chat`` on one TPU v5 lite, in ``bench.trace.compact``'s
form: one 32-row chunk's prefill (128 prompt tokens) and its first three
decode steps, with the device's operations and the host's events.  The
numbers below were read off it by hand: the prefill module lasted
131,795,871 ns, the three decode modules 50,729,907 ns together, and
operations covered 183,369,762 ns of the 183,623,922 ns window.
"""

from __future__ import annotations

import gzip
import json
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

from bench import harness, trace

dense_decoder = harness._module("models", "dense_decoder")

FIXTURE = Path(__file__).parent / "data" / "trace_qwen_chat_v5e.json.gz"
WINDOW_NS, BUSY_NS = 183_623_922, 183_369_762


@pytest.fixture(scope="module")
def tr():
    with gzip.open(FIXTURE, "rt") as f:
        return json.load(f)


@pytest.fixture(scope="module")
def run(tr):
    """What the readers see of a run whose traced stretch is the fixture:
    one chunk of 32 rows, 128-token prompts, three decode steps, and one
    scheduler step spanning the stretch."""
    config = json.loads((harness.BENCH / "configs" / "qwen2.5-3b.json")
                        .read_text())
    mark = 100.0
    return SimpleNamespace(
        trace=tr, traced=[(32, 1)], trace_mark_perf=mark,
        cell=SimpleNamespace(traffic={"prompt_len": 128, "gen": 4}),
        counts=dense_decoder.counts(config),
        peak=harness.peak_of("TPU v5 lite"),
        steps=[{"row_done_at": np.array([mark + WINDOW_NS / 1e9]),
                "t_step": WINDOW_NS / 1e9}])


def test_window_busy_and_programs(tr):
    assert tr["t1_ns"] - tr["t0_ns"] == WINDOW_NS
    assert trace.window_s(tr) == WINDOW_NS / 1e9
    assert trace.busy_s(tr) == BUSY_NS / 1e9
    assert trace.program(tr, trace.PREFILL) == (0.131795871, 1)
    assert trace.program(tr, trace.DECODE) == (0.050729907, 3)


def test_breakdown(tr):
    ops = trace.top_ops(tr, 3)
    assert ops[0] == ["%fusion.132 bf16[32,128,2048] fusion", 0.036195025]
    assert [o[0] for o in ops[1:]] == [
        "%convolution_multiply_fusion.2 bf16[32,128,11008] fusion",
        "%fusion.131 bf16[32,128,11008] fusion"]
    assert all(not o[0].endswith(" while") for o in trace.top_ops(tr))
    assert trace.idle_gaps(tr) == [["no host event", 0.000200279]]


def test_readers_on_the_recorded_trace(run):
    def read(name):
        return harness._module("metrics", name).read(run)

    assert read("prefill_us_per_token") == pytest.approx(
        0.131795871 / (32 * 128) * 1e6)                   # 32.18 us
    assert read("decode_ms_per_step") == pytest.approx(50.729907 / 3)
    # 22.83 TFLOP at 197 TFLOP/s (compute-bound) over 131.8 ms
    assert read("prefill_roofline") == pytest.approx(87.9178, abs=1e-3)
    # each step reads 6.34 GB at 819 GB/s (memory-bound), 7.75 ms, over
    # 16.9 ms
    assert read("decode_roofline") == pytest.approx(45.8209, abs=1e-3)
    assert read("device_idle_share") == pytest.approx(
        100 * (WINDOW_NS - BUSY_NS) / WINDOW_NS, rel=1e-6)


def test_readers_find_nothing_without_a_trace(run):
    bare = SimpleNamespace(**{**vars(run), "trace": None})
    for name in ("prefill_us_per_token", "decode_ms_per_step",
                 "prefill_roofline", "decode_roofline", "device_idle_share"):
        assert harness._module("metrics", name).read(bare) is None


def test_interval_arithmetic():
    # (start, duration) pairs
    assert trace.union([(0, 5), (3, 4), (10, 2), (11, 5)]) == [(0, 7), (10, 16)]
    assert trace.overlap([(0, 5), (10, 16)], 4, 12) == 3
    tr = {"t0_ns": 0, "t1_ns": 1_000_000, "host": [["h", 0, 600_000]],
          "devices": [{"name": "/device:TPU:0", "modules": [],
                       "ops": [["a", 100_000, 300_000],
                               ["b", 700_000, 200_000]]}]}
    assert trace.busy_s(tr) == 0.0005
    assert trace.idle_gaps(tr) == [["h", 0.0003], ["h", 0.0001],
                                   ["no host event", 0.0001]]


def test_op_label():
    assert trace.op_label(
        "%copy.107 = f32[32,256,2,128]{3,2,1,0:T(2,128)S(1)} copy(bf16[32,256"
        ",2,128]{3,0,2,1:T(8,128)(2,1)S(1)} %x)") == "%copy.107 f32[32,256,2,128] copy"
