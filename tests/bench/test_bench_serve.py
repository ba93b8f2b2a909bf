"""A whole run of the harness on the CPU at a small size, skipping only
its look for a chip: served through ``serve_requests``, measured, and
judged against the reference.  A sound run is correct; a run whose timed
path is broken underneath is not; and the reference one precision down
(the control) reads far above the served model.
"""

from __future__ import annotations

import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from bench import harness
from bench.reference import dense_decoder as ref
from small_cell import small_cell

SEED = 3_000_000_017          # above 2**31, as the driver's seeds are


def serve(fault=None, seed=SEED):
    return harness.run_cell(small_cell(), seed=seed, seconds=1.5,
                            traced=False, devices=jax.devices()[:1],
                            t0=time.perf_counter(), fault=fault)


@pytest.fixture(scope="module")
def sound():
    return serve()


def test_sound_run_is_correct_and_reports_the_cell(sound):
    run = sound["_run"]
    assert sound["correct"], sound["checks"]
    assert sound["attempted"] == run.n_requests == 30
    assert sound["failed"] == 0 and not run.step_errors
    assert set(sound["metrics"]) == {m["name"] for m in run.cell.end_to_end}
    assert all(v["value"] > 0 for v in sound["metrics"].values())
    assert list(sound)[-2:] == ["checks", "_run"]
    assert len(run.sample["rows"]) >= 8
    assert run.compiles_in_window == 0


def _alter_one_token(tokens, res):
    return {**res, "tokens": res["tokens"].at[:, 3].add(1)}


def _leave_out_every_other_row(tokens, res):
    keep = (jnp.arange(tokens.shape[0]) % 2 == 0)[:, None]
    return {"tokens": jnp.where(keep, res["tokens"], 0),
            "logits": jnp.where(keep, res["logits"], 0.0)}


@pytest.mark.parametrize("fault", [_alter_one_token,
                                   _leave_out_every_other_row])
def test_a_broken_output_is_not_correct(fault):
    assert not serve(fault)["correct"]


def test_a_decode_that_keeps_its_state_is_not_correct(monkeypatch):
    from repro.models.lm import LM
    step = LM.decode_step

    def stale(self, params, state, tokens, pos):
        logits, _ = step(self, params, state, tokens, pos)
        return logits, state

    monkeypatch.setattr(LM, "decode_step", stale)
    assert not serve()["correct"]


def test_the_control_reads_far_above_the_served_model(sound):
    run = sound["_run"]
    s = run.sample
    cfg = run.cell.config
    served = ref.readings(cfg, run.seed, s["prompts"], s["tokens"], s["logits"])
    control = ref.readings(cfg, run.seed, s["prompts"], s["tokens"],
                           s["logits"], control=True)
    assert served == {k: c["value"] for k, c in sound["checks"].items()}
    for name in served:
        assert control[name] > 3 * served[name], (name, served, control)
    assert not harness.correct({k: {"value": v, "limit": lim} for (k, v), lim
                                in zip(control.items(),
                                       run.cell.settings["check"]["limits"]
                                       .values())})


def test_emit_prints_checks_last(sound, capsys):
    import json
    line = dict(sound)
    harness.emit(line)
    out, err = capsys.readouterr()
    assert list(json.loads(out.strip().splitlines()[-1])) == [
        "correct", "attempted", "failed", "metrics", "device", "checks"]
    assert err.strip().splitlines()[-1].startswith("check prefill_logit_err")
    assert np.isfinite(json.loads(out)["checks"]["token_gap"]["value"])
