"""Roofline machinery validation.

1. Analytic ledger vs XLA cost_analysis on a 1-group config (scan body
   counted once == the whole model, so the comparison is apples-to-apples).
2. Trip-weighted collective census vs a hand-built program with known
   loop trips and collective sizes (subprocess, 8 devices).
3. Roofline term arithmetic.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from helpers import run_subprocess

from repro import configs
from repro.dist.sharding import ShardingConfig
from repro.launch.shapes import ShapeCell
from repro.roofline import analysis


def test_analytic_flops_vs_xla_cost_analysis():
    """1-layer (single-group) model: ledger fwd FLOPs within 20 % of XLA."""
    base = configs.get("qwen2.5-3b")
    cfg = dataclasses.replace(
        base, n_layers=1, layer_kinds=("attn",), d_model=256, n_heads=4,
        n_kv_heads=2, head_dim=64, d_ff=512, vocab_size=1024,
        param_dtype="float32", compute_dtype="float32", logit_chunk=64,
        tie_embeddings=False, qkv_bias=False)
    from repro.models import build_model
    model = build_model(cfg)
    b, t = 4, 256
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    batch = {"tokens": jax.ShapeDtypeStruct((b, t), jnp.int32),
             "labels": jax.ShapeDtypeStruct((b, t), jnp.int32)}

    def fwd(p, bt):
        return model.loss(p, bt)[0]

    compiled = jax.jit(fwd).lower(params, batch).compile()
    xla_flops = compiled.cost_analysis()["flops"]

    cell = ShapeCell("probe", "train", t, b)
    scfg = ShardingConfig(remat=False, fsdp_axes=(), microbatches=1)
    ledger = analysis.analytic_cost(cfg, cell, scfg, n_chips=1)
    # ledger counts fwd*3 for train; compare the fwd component
    fwd_analytic = ledger.flops / 3.0
    assert 0.8 <= fwd_analytic / xla_flops <= 1.25, \
        f"analytic {fwd_analytic:.3e} vs xla {xla_flops:.3e}"


def test_census_trip_weighting():
    out = run_subprocess("""
import jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.launch.mesh import make_mesh
from repro.roofline.hlo import collective_census
mesh = make_mesh((8,), ("d",))

def step(x, _):
    # explicit psum inside the scan body -> a real all-reduce per trip
    local = jax.shard_map(lambda xl: xl + 1e-3 * jax.lax.psum(xl, "d"),
                          mesh=mesh, in_specs=P("d", None),
                          out_specs=P("d", None), check_vma=False)(x)
    return local, None

def fn(x):
    y, _ = jax.lax.scan(step, x, None, length=12)
    return y.sum()

with jax.set_mesh(mesh):
    c = jax.jit(fn, in_shardings=NamedSharding(mesh, P("d", None)),
                out_shardings=NamedSharding(mesh, P())) \
        .lower(jax.ShapeDtypeStruct((64, 64), jnp.float32)).compile()
census = collective_census(c.as_text())
loops = [l for l in census["loops"] if l["trips"] == 12]
assert loops, census["loops"]
raw = sum(v["count"] for v in census["raw"].values())
weighted = sum(v["count"] for v in census["weighted"].values())
assert weighted >= raw + 11, (raw, weighted)   # body collectives x 12
print("CENSUS_OK", raw, weighted)
""")
    assert "CENSUS_OK" in out


def test_roofline_terms_arithmetic():
    ledger = analysis.Ledger(flops=197e12 * 256, hbm_bytes=819e9 * 0.5)
    ledger.model_flops = 197e12 * 256 * 0.5
    terms = analysis.roofline_terms(ledger, 50e9 * 0.25, 256)
    assert terms["compute_s"] == pytest.approx(1.0)
    assert terms["memory_s"] == pytest.approx(0.5)
    assert terms["collective_s"] == pytest.approx(0.25)
    assert terms["dominant"] == "compute_s"
    assert terms["mfu_bound"] == pytest.approx(0.5)


def test_model_flops_bands():
    cell = ShapeCell("train_4k", "train", 4096, 256)
    for name in ("qwen2.5-3b", "nemotron-4-340b"):
        cfg = configs.get(name)
        mf = analysis.model_flops(cfg, cell)
        expect = 6 * cfg.param_count() * 4096 * 256
        assert 0.9 <= mf / expect <= 1.1


def test_analytic_memory_fits_claim():
    """Independent per-chip footprint for the §Dry-run capacity claims."""
    cell = ShapeCell("train_4k", "train", 4096, 256)
    cfg = configs.get("nemotron-4-340b")
    # bf16 params + f32 grads + int8 moments, all sharded over 256 chips
    n = cfg.param_count()
    per_chip = (2 * n + 4 * n + 2 * n) / 256 / 2**30
    assert per_chip < 16.0, f"{per_chip:.1f} GiB > HBM"
