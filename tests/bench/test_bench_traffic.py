"""The traffic mixes name their sources, and the harness holds the
program's arrival source to the mix from the served records alone."""

from __future__ import annotations

import json
import time

import numpy as np
import pytest

import jax

from bench import harness, traffic
from small_cell import small_cell

MIXES = sorted(p.stem for p in (harness.BENCH / "traffic").glob("*.json"))


@pytest.mark.parametrize("mix", MIXES)
def test_every_length_and_deadline_names_its_source(mix):
    tr = json.loads((harness.BENCH / "traffic" / f"{mix}.json").read_text())
    assert set(tr["sources"]) >= {"prompt_len", "gen", "slo_s"}
    assert all(len(text) > 20 for text in tr["sources"].values())
    assert sum(c["weight"] for c in tr["classes"]) == pytest.approx(1.0)


MIX = {"prompt_len": 32, "gen": 8, "rows_choices": [1, 2, 4],
       "classes": [{"name": "chat"}]}
RATE, N = 4.0, 200


def _records(seed=0):
    rng = np.random.default_rng(seed)
    t = np.cumsum(rng.exponential(1 / RATE, N))
    return [{"rid": i, "shape": [32, 8], "rows": int(rng.choice([1, 2, 4])),
             "klass": "chat", "t_arrival": float(t[i])} for i in range(N)]


def test_the_mix_as_offered_departs_in_nothing():
    for seed in range(5):
        assert traffic.departures(MIX, RATE, N, _records(seed), 0.0) == []


@pytest.mark.parametrize("change, words", [
    (lambda rs: rs[:-1], "requests served"),
    (lambda rs: [{**r, "shape": [64, 8]} for r in rs], "shape"),
    (lambda rs: [{**r, "rows": 3} for r in rs], "rows"),
    (lambda rs: [{**r, "klass": "batch"} for r in rs], "class"),
    (lambda rs: [{**r, "t_arrival": r["t_arrival"] * 2} for r in rs],
     "requests/s"),
])
def test_a_source_that_drifts_from_the_mix_is_named(change, words):
    off = traffic.departures(MIX, RATE, N, change(_records()), 0.0)
    assert any(words in o for o in off), off


def test_a_cell_past_the_sliding_window_is_refused():
    cell = small_cell("phi3-mini-3.8b", "docqa")
    cell = harness.Cell(cell.name, {**cell.config, "sliding_window": 39},
                        cell.traffic, cell.settings, cell.chips,
                        cell.end_to_end, cell.per_layer)
    with pytest.raises(harness.BenchError, match="sliding_window"):
        harness.serve(cell, seed=5, seconds=1.0, devices=jax.devices()[:1],
                      t0=time.perf_counter())
