"""An architecture reaches the harness as files alone.

A configuration's ``reference`` names its architecture module,
``bench/models/<reference>.py``, beside its plain reference,
``bench/reference/<reference>.py``.  A name with no module is refused as
a missing benchmark file before anything runs, and a module placed under
a copy of ``bench/models`` is served by that name with no change to
``bench/harness.py``.
"""

from __future__ import annotations

import json
import shutil
import time
from dataclasses import replace

import pytest

import jax

from bench import harness
from small_cell import small_cell


@pytest.fixture
def copy(tmp_path, monkeypatch):
    """A copy of the benchmark's files, which the harness then reads."""
    shutil.copy(harness.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(harness.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    monkeypatch.setattr(harness, "ROOT", tmp_path)
    monkeypatch.setattr(harness, "BENCH", tmp_path / "bench")
    return tmp_path


def _rename_reference(copy, config, reference):
    path = copy / "bench" / "configs" / f"{config}.json"
    path.write_text(json.dumps({**json.loads(path.read_text()),
                                "reference": reference}))


def test_a_reference_with_no_architecture_module_is_refused(copy):
    _rename_reference(copy, "phi3-mini-3.8b", "no_such_family")
    with pytest.raises(harness.BenchError,
                       match=r"missing benchmark file .*bench/models/"
                             r"no_such_family\.py"):
        harness.Cell.resolve("phi3-mini-3.8b.docqa")
    with pytest.raises(harness.BenchError, match="missing benchmark file"):
        harness._module("models", "no_such_family")


def test_a_new_architecture_module_is_served_by_its_name(copy):
    bench = copy / "bench"
    (bench / "models" / "toy_decoder.py").write_text(
        (bench / "models" / "dense_decoder.py").read_text()
        + "\n\nFAMILY = 'toy_decoder'\n")
    shutil.copy(bench / "reference" / "dense_decoder.py",
                bench / "reference" / "toy_decoder.py")
    _rename_reference(copy, "phi3-mini-3.8b", "toy_decoder")
    resolved = harness.Cell.resolve("phi3-mini-3.8b.docqa")
    assert resolved.config["reference"] == "toy_decoder"
    assert resolved.architecture.FAMILY == "toy_decoder"

    cell = small_cell("phi3-mini-3.8b", "docqa")
    line = harness.run_cell(cell, seed=3_000_000_041, seconds=1.0,
                            traced=False, devices=jax.devices()[:1],
                            t0=time.perf_counter())
    run = line.pop("_run")
    assert type(run.counts).__module__ == "bench_models_toy_decoder"
    assert line["correct"] and line["failed"] == 0, line["checks"]


def test_the_dense_module_refuses_another_family():
    cell = harness.Cell.resolve("phi3-mini-3.8b.docqa")
    assert cell.arch_config(positions=1056).n_layers == 32
    other = replace(cell, config={**cell.config, "arch": "jamba-v0.1-52b"})
    with pytest.raises(harness.BenchError, match="not the dense SwiGLU"):
        other.arch_config(positions=1056)
