"""The serving path's profiler spans and the request records' counters.

Every span named in ``repro.obs.SPANS`` is a ``jax.profiler``
annotation, so a real run under ``jax.profiler.trace`` carries the
engine's, the scheduler's and the step builder's host spans in the same
trace as the device's events.  The request records carry the admission
instant and the engine step that served them, which split the queue
wait into the wait for admission and the wait in the batcher.
"""

from __future__ import annotations

import numpy as np
import pytest

import jax

from repro import configs
from repro.core.hetero import DeviceGroup
from repro.launch.serve import serve_requests
from repro.obs import SPANS
from repro.runtime.simulate import FaultPlan
from repro.serve import BatcherConfig, RequestClass, make_sim_engine


def test_sim_records_carry_admission_lag_and_step():
    plan = FaultPlan().transient(0, at=3).transient(1, at=3)
    eng = make_sim_engine(n_requests=120, rate_rps=2000.0, seed=11,
                          fault_plan=plan, guard=True)
    s = eng.run()
    assert s["retries"] > 0                # a retry keeps its first admit
    done = [r for r in eng.done if r.status == "completed"]
    assert len(done) == s["completed"] > 0
    for r in done:
        rec = r.record()
        assert rec["t_admit"] == r.t_admit is not None
        assert rec["admit_lag_s"] >= 0
        assert isinstance(rec["step"], int)
        assert 1 <= rec["step"] <= eng.steps
        assert rec["queue_delay_s"] == pytest.approx(
            rec["admit_lag_s"] + (r.t_dispatch - r.t_admit), abs=1e-9)
    # every group failed step 3: its requests were served by a later one
    assert all(r.step > 3 for r in done if r.retries)


def _host_events(log_dir):
    from jax.profiler import ProfileData
    found = sorted(log_dir.glob("plugins/profile/*/*.xplane.pb"))
    data = ProfileData.from_file(str(found[-1]))
    out = []                       # (line, name, start, end, args)
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for li, line in enumerate(plane.lines):
            for e in line.events:
                args = dict(e.stats) if e.name in SPANS else {}
                out.append((li, e.name, int(e.start_ns),
                            int(e.start_ns + e.duration_ns), args))
    return out


def _inside(inner, outers):
    line, _, s, e, _ = inner
    return any(o[0] == line and o[2] <= s and e <= o[3] for o in outers)


def test_serving_spans_in_the_profiler_trace(tmp_path):
    cfg = configs.get("qwen2.5-3b").smoke()
    groups = [DeviceGroup("g0", jax.devices()[:1])]
    with jax.profiler.trace(str(tmp_path)):
        out = serve_requests(cfg, groups=groups, n_requests=10,
                             rate_rps=40.0, prompt_len=32, gen=4, seed=2,
                             row_quantum=4,
                             classes=(RequestClass("batch", slo_s=60.0),),
                             batcher_config=BatcherConfig(max_batch_rows=8))
    out["scheduler"].close()
    assert out["summary"]["completed"] == 10
    events = _host_events(tmp_path)
    named = {name for _, name, *_ in events}
    assert set(SPANS) <= named
    # every span of the serving path is in the table
    assert {n for n in named
            if n.split(".")[0] in ("serve", "sched", "step")} == set(SPANS)

    def spans(name):
        return [e for e in events if e[1] == name]

    steps = spans("serve.step")
    done = [r for r in out["records"] if r["status"] == "completed"]
    assert {r["step"] for r in done} <= {e[4]["step"] for e in steps}
    assert all(e[4]["rows"] <= e[4]["padded_rows"] for e in steps)
    dispatches = spans("sched.dispatch")
    for e in spans("step.prefill") + spans("step.decode"):
        assert _inside(e, dispatches), e
    # the warm-up steps before the first arrival run outside the engine
    first = min(e[2] for e in steps)
    served = [e for e in dispatches if e[2] >= first]
    assert served and all(_inside(e, steps) for e in served)
    assert all(e[4]["rows"] > 0 for e in dispatches)
    # the drain runs on its own thread, the wait on the engine's
    drain_lines = {e[0] for e in spans("sched.drain")}
    assert drain_lines.isdisjoint({e[0] for e in steps})
    assert all(_inside(e, steps) for e in spans("sched.wait")
               if e[2] >= first)
    waits = np.asarray([[r["queue_delay_s"], r["admit_lag_s"]]
                        for r in done])
    assert (waits[:, 1] >= 0).all() and (waits[:, 0] >= waits[:, 1]).all()
