"""BENCHMARK.json resolves to files, and every file agrees with the program.

Each workload must find its configuration, traffic mix, cell settings,
reference and one reader per metric, by name; the configuration files
must state the widths the program's registry runs; the names, units and
bounds must keep the benchmark contract's forms.
"""

from __future__ import annotations

import json
import re

import pytest

from bench import harness

SPEC = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
WORKLOADS = [w["name"] for w in SPEC["workloads"]]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def test_top_level_keys_and_command():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert SPEC["command"] == ["python3", "bench/run.py"]
    for path in SPEC["paths"]:
        assert (harness.ROOT / path).is_dir()
    assert 1 <= SPEC["run_seconds"] <= 51


def test_a_full_check_of_24_cells_fits_its_budget():
    per_run = SPEC["run_seconds"] + 60
    total = (2 + 14 * 24) * per_run + 24 * 2 * 90 + 1200
    assert total <= 43200


@pytest.mark.parametrize("workload", WORKLOADS)
def test_workload_resolves_every_file(workload):
    cell = harness.Cell.resolve(workload)
    assert cell.chips in (1, 4)
    assert cell.chips == cell.config["deployment"]["chips"]
    for metric in cell.end_to_end + cell.per_layer:
        assert callable(harness._module("metrics", metric["name"]).read)
    ref = harness._module("reference", cell.config["reference"])
    assert callable(ref.readings)
    arch = cell.architecture
    assert callable(arch.arch_config) and callable(arch.counts)
    assert set(cell.settings["check"]["limits"]) == {"token_gap",
                                                     "prefill_logit_err"}
    assert cell.traffic["arrival"] == "poisson"
    names = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in names and len(names) >= 2 and cell.per_layer


@pytest.mark.parametrize("workload", WORKLOADS)
def test_configuration_file_states_what_the_program_runs(workload):
    """The file's widths are the registry's; its epsilon is the published
    one, which the harness hands the program (the registry's qwen2.5-3b
    keeps ArchConfig's default)."""
    from dataclasses import replace
    from repro import configs
    cell = harness.Cell.resolve(workload)
    cfg = cell.architecture.arch_config(cell.config)
    base = configs.get(cell.config["arch"])
    assert cfg.norm_eps == cell.config["rms_norm_eps"]
    assert replace(cfg, norm_eps=base.norm_eps) == base


def test_names_units_bounds_and_layers():
    metrics = SPEC["end_to_end"] + SPEC["per_layer"]
    names = [m["name"] for m in metrics]
    assert len(set(names)) == len(names)
    for m in metrics:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        for w in m.get("workloads", []):
            assert w in WORKLOADS
    for m in SPEC["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    assert {m["name"]: m["bound"] for m in SPEC["end_to_end"]}["setup_s"] == 0.25
    moves = {m["name"] for m in SPEC["end_to_end"]}
    for m in SPEC["per_layer"]:
        assert m["moves"] in moves and "\n" not in m["layer"]
        if m["name"].endswith("_roofline") or "mfu" in m["name"]:
            assert m["unit"] == "%"


def test_free_text_fields_are_one_short_line():
    texts = [e["why"] for e in SPEC["configs"] + SPEC["workloads"]]
    texts += [c["source"] for c in SPEC["configs"]]
    texts += [m["layer"] for m in SPEC["per_layer"]] + SPEC["command"]
    for text in texts:
        assert 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_configs_and_cells_are_named_and_used():
    used = {w["config"] for w in SPEC["workloads"]}
    files = [c["file"] for c in SPEC["configs"]]
    assert len(set(files)) == len(files)
    for c in SPEC["configs"]:
        assert c["name"] in used and NAME.match(c["name"])
        config = json.loads((harness.ROOT / c["file"]).read_text())
        assert c["source"] == config["source"]
        assert c["reduced"] == config["reduced"]
        for key in c["reduced"]:
            assert key in config and not key.endswith(("_dim", "_rank"))
    pairs = [(w["config"], w["traffic"]) for w in SPEC["workloads"]]
    assert len(set(pairs)) == len(pairs)
    four = sum(w["chips"] == 4 for w in SPEC["workloads"])
    assert four <= max(1, len(WORKLOADS) // 2)
