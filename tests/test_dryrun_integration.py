"""Dry-run integration: two fast cells lower+compile on production meshes
(subprocess with 512 placeholder devices, like the real dryrun)."""

import pytest

from helpers import run_subprocess


@pytest.mark.parametrize("arch,cell,mesh", [
    ("whisper-base", "prefill_32k", "single"),
    ("rwkv6-1.6b", "long_500k", "multi"),
    ("qwen2.5-3b", "decode_32k", "single"),
])
def test_dryrun_cell_compiles(arch, cell, mesh):
    out = run_subprocess(f"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
from repro.launch import dryrun, shapes
rec = dryrun.run_cell("{arch}", shapes.SHAPE_CELLS["{cell}"], "{mesh}")
assert rec["ok"], rec.get("error")
assert rec["memory"]["peak_per_device_gb"] < 16.0, rec["memory"]
assert rec["cost_analysis"]["flops"] > 0
print("CELL_OK", rec["memory"]["peak_per_device_gb"])
""", devices=512, timeout=900)
    assert "CELL_OK" in out


def test_input_specs_cover_all_cells():
    out = run_subprocess("""
from repro import configs
from repro.launch import shapes
n = 0
for name in configs.ARCH_NAMES:
    cfg = configs.get(name)
    for cell in shapes.SHAPE_CELLS.values():
        ok, why = shapes.applicable(cfg, cell)
        if not ok:
            assert "quadratic" in why
            continue
        specs = shapes.batch_specs_for(cfg, cell)
        assert specs, (name, cell.name)
        n += 1
assert n == 36, n
print("SPECS_OK", n)
""", devices=1)
    assert "SPECS_OK 36" in out
