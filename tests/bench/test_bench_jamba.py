"""The Jamba hybrid at a small size on the CPU: its architecture module
against hand counts, its plain reference against the program (weights,
``LM.prefill`` + ``decode_step``, and the serving step builder), the
comparison failing where the program leaves out part of the Mamba mixer,
a whole harness run of its cell, and the scan readers."""

from __future__ import annotations

import json
import time
from dataclasses import replace
from types import SimpleNamespace

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from bench import harness
from bench.reference import jamba as ref
from small_cell import SETTINGS

arch = harness._module("models", "jamba")
scan_us = harness._module("metrics", "scan_us_per_token")
scan_roof = harness._module("metrics", "scan_roofline")

CONFIG = json.loads((harness.BENCH / "configs" / "jamba2-3b.json").read_text())
# two periods of (Mamba, attention); 4 heads of 16 over one KV head
SMALL = {"hidden_size": 64, "intermediate_size": 128, "num_hidden_layers": 4,
         "attn_layer_period": 2, "attn_layer_offset": 1,
         "num_attention_heads": 4, "vocab_size": 256, "mamba_dt_rank": 8}
SEED = 2 ** 31 + 23456
# At these widths the program in bfloat16 reads prefill_logit_err 0.011-0.042
# against the reference on the CPU, the float8 control 0.15-0.22, and
# token_gap 0 and 0-0.083 (5 seeds); the limits lie between.
SMALL_SETTINGS = {**SETTINGS, "check": {**SETTINGS["check"], "limits": {
    "token_gap": 0.015, "prefill_logit_err": 0.08}}}
LIMITS = SMALL_SETTINGS["check"]["limits"]


def small_config() -> dict:
    return {**CONFIG, **SMALL}


def small_model(config=None, compute="float32"):
    from repro.models import build_model
    cfg = replace(arch.arch_config(config or small_config()),
                  param_dtype="bfloat16", compute_dtype=compute)
    return build_model(cfg)


# -- the architecture ------------------------------------------------------------

def test_arch_config_is_the_registry_entry_at_the_file_widths():
    from repro import configs
    cfg = arch.arch_config(CONFIG)
    assert cfg == configs.get("jamba2-3b")
    assert [i for i, k in enumerate(cfg.layer_kinds) if k == "attn"] == [7, 21]
    small = arch.arch_config(small_config())
    assert small.layer_kinds == ("mamba", "attn") * 2
    assert small.mamba.dt_rank == 8


@pytest.mark.parametrize("key, value", [
    ("mamba_proj_bias", True), ("num_experts", 16), ("sliding_window", 4096),
    ("hidden_act", "gelu"), ("num_hidden_layers", 27),
    ("arch", "jamba2-3b-not-registered")])
def test_arch_config_refuses_what_the_program_does_not_run(key, value):
    with pytest.raises(ValueError):
        arch.arch_config({**CONFIG, key: value})


def test_counts_against_hand_counts():
    c = arch.counts(CONFIG)
    assert (c.layers, c.attn_layers, c.mamba_layers) == (28, 2, 26)
    # in_proj 2560 x 10240, x_proj 5120 x 192, dt_proj 160 x 5120,
    # out_proj 5120 x 2560
    assert c.mamba_matmul_params == 26_214_400 + 983_040 + 819_200 \
        + 13_107_200
    assert c.attn_matmul_params == 2560 * 22 * 128 + 20 * 128 * 2560
    assert c.kv_bytes_per_token == 2 * 2 * 128 * 2           # 1 KiB
    assert c.ssm_bytes_per_row == 26 * (5120 * 16 * 4 + 3 * 5120 * 2)
    # one layer's scan over one 8192-token row: 115 flops per channel
    # and token; dt, x, y of 5120 and B, C of 16 floats per token
    assert c.scan(1, 8192) == (8192 * 5120 * 115.0,
                               4.0 * 8192 * (3 * 5120 + 32))
    assert c.scan(2, 8192) == tuple(2 * v for v in c.scan(1, 8192))
    # every parameter is read once, 3.03 B of them, about 6.06 GB
    from repro import configs
    n = configs.get("jamba2-3b").param_count()
    assert abs(c.weight_bytes - 2 * n) < 0.002 * 2 * n
    f1, b1 = c.prefill(1, 8192)
    pairs = 8192 * 8193 / 2
    assert f1 == pytest.approx(8192 * (2 * c.matmul_params + 26 * (
        2 * 4 * 5120 + 5120 * 115)) + 4 * 2 * 20 * 128 * pairs
        + 2 * 2560 * 65536)
    assert b1 == c.weight_bytes + 8192 * 1024 + c.ssm_bytes_per_row \
        + 65536 * 4
    fd, bd = c.decode(4, 8200)
    assert bd == c.weight_bytes + 4 * 8201 * 1024 + 4 * 1024 \
        + 2 * 4 * c.ssm_bytes_per_row + 4 * 65536 * 4
    assert c.served_row_flops(8192, 32) == pytest.approx(
        f1 + sum(c.decode(1, 8192 + i)[0] for i in range(31)))


# -- the reference against the program -------------------------------------------

def test_weights_are_the_served_weights():
    config = small_config()
    params = jax.jit(small_model(config, "bfloat16").init)(
        jax.random.PRNGKey(SEED))
    m = ref._dims(config)
    k_emb, k_layers, _ = jax.random.split(jax.random.PRNGKey(SEED), 3)
    groups = jax.random.split(k_layers, m["L"] // m["period"])
    for i in range(m["L"]):
        g, j = divmod(i, m["period"])
        key = jax.random.split(groups[g], m["period"])[j]
        layer = params["layers"][f"slot{j}"]
        mixer = {k: v[g] for k, v in layer["mixer"].items()}
        if i % m["period"] == m["offset"]:
            mine = ref._attn_weights(key, m, control=False)
        else:
            mine = ref._mamba_weights(key, m, control=False)
            # XLA may round log(n) to a neighbouring float in another
            # fusion; everything else is equal bit for bit
            np.testing.assert_allclose(np.asarray(mine.pop("A_log")),
                                       np.asarray(mixer["A_log"]).T,
                                       rtol=2e-7)
            np.testing.assert_array_equal(np.asarray(mixer["conv_b"]), 0)
            for n in ("dt_norm", "b_norm", "c_norm"):
                np.testing.assert_array_equal(np.asarray(mixer[n]), 1)
        mine.update(ref._mlp_weights(key, m, control=False))
        mixer.update({k: v[g] for k, v in layer["channel"].items()})
        for name, w in mine.items():
            np.testing.assert_array_equal(
                np.asarray(w), np.asarray(mixer[name], np.float32),
                f"layer {i} {name}")
    table = ref._served(jax.random.normal(jax.random.split(k_emb, 2)[0],
                                          (m["V"], m["d"])) * 0.02)
    np.testing.assert_array_equal(
        np.asarray(table), np.asarray(params["embed"]["tokens"], np.float32))


def test_reference_logits_match_the_model_in_float32():
    config = small_config()
    model = small_model(config)
    params = model.init(jax.random.PRNGKey(SEED))
    prompt, gen = 8, 6
    seq = np.random.default_rng(0).integers(0, SMALL["vocab_size"],
                                            (2, prompt + gen - 1),
                                            dtype=np.int32)
    logits, state = model.prefill(params, jnp.asarray(seq[:, :prompt]),
                                  max_len=prompt + gen)
    got = [np.asarray(logits[:, -1])]
    for i in range(gen - 1):
        logits, state = model.decode_step(
            params, state, jnp.asarray(seq[:, prompt + i:prompt + i + 1]),
            jnp.int32(prompt + i))
        got.append(np.asarray(logits[:, -1]))
    want = ref.logits(config, SEED, seq, prompt - 1)
    np.testing.assert_allclose(np.stack(got, 1), want, rtol=2e-4,
                               atol=2e-4 * np.abs(want).max())


def _served_readings(prompt=8, gen=8):
    """The step builder's tokens and prefill logits for four prompts,
    read against the reference."""
    from repro.core.hetero import DeviceGroup
    from repro.launch.serve import _stream_step_builder
    config = small_config()
    fn = _stream_step_builder(small_model(config), prompt_len=prompt,
                              gen=gen, seed=SEED)(
        DeviceGroup("all", jax.devices()[:1]))
    prompts = np.random.default_rng(1).integers(
        0, SMALL["vocab_size"], (4, prompt), dtype=np.int32)
    out = fn({"tokens": prompts})
    return ref.readings(config, SEED, prompts, np.asarray(out["tokens"]),
                        np.asarray(out["logits"], np.float32))


def test_served_tokens_and_prefill_logits_match_the_reference():
    got = _served_readings()
    assert got["token_gap"] < 1e-4 and got["prefill_logit_err"] < 1e-5, got


def _no_inner_norms(monkeypatch):
    from repro.models import mamba
    monkeypatch.setattr(mamba, "_rms", lambda x, scale, eps: x)


def _no_skip(monkeypatch):
    from repro.models import blocks
    init = blocks.init_mamba
    monkeypatch.setattr(blocks, "init_mamba", lambda key, cfg: {
        **init(key, cfg), "D": jnp.zeros((2 * cfg.d_model,), jnp.float32)})


def _no_carried_conv(monkeypatch):
    from repro.models import blocks
    decode = blocks.decode_mamba
    monkeypatch.setattr(blocks, "decode_mamba", lambda p, x, state, cfg: decode(
        p, x, {**state, "conv": jnp.zeros_like(state["conv"])}, cfg))


@pytest.mark.parametrize("fault", [_no_inner_norms, _no_skip,
                                   _no_carried_conv])
def test_the_comparison_fails_without_each_part_of_the_mixer(monkeypatch,
                                                             fault):
    fault(monkeypatch)
    got = _served_readings()
    assert not harness.correct({k: {"value": got[k], "limit": v}
                                for k, v in LIMITS.items()}), got


def test_the_control_reads_far_above_the_served_model():
    got = _served_readings()
    config = small_config()
    prompts = np.random.default_rng(1).integers(0, SMALL["vocab_size"],
                                                (4, 8), dtype=np.int32)
    served = np.random.default_rng(2).integers(0, SMALL["vocab_size"],
                                               (4, 8), dtype=np.int32)
    want = ref.logits(config, SEED, np.concatenate([prompts, served[:, :-1]],
                                                   1), 7)
    control = ref.readings(config, SEED, prompts, served, want[:, 0],
                           control=True)
    assert control["prefill_logit_err"] > 100 * got["prefill_logit_err"]


# -- a whole harness run of the cell --------------------------------------------

def test_the_cell_serves_correctly_through_the_harness():
    spec = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
    mix = json.loads((harness.BENCH / "traffic" / "longdoc.json").read_text())
    config = {**small_config(),
              "deployment": {**CONFIG["deployment"], "max_batch_rows": 8}}
    cell = harness.Cell("jamba2-3b.longdoc", config,
                        {**mix, "prompt_len": 32, "gen": 8}, SMALL_SETTINGS, 1,
                        tuple(spec["end_to_end"]),
                        tuple(m for m in spec["per_layer"]
                              if "jamba2-3b.longdoc" in m["workloads"]))
    out = harness.run_cell(cell, seed=SEED, seconds=1.5, traced=False,
                           devices=jax.devices()[:1], t0=time.perf_counter())
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and not out["_run"].step_errors
    assert set(out["metrics"]) == {m["name"] for m in cell.end_to_end}


# -- the scan readers --------------------------------------------------------------

def _run(ops, traced=((4, 1), (2, 1))):
    return SimpleNamespace(
        trace={"devices": [{"ops": ops, "modules": []}]},
        traced=list(traced), cell=SimpleNamespace(traffic={"prompt_len": 8192}),
        counts=arch.counts(CONFIG), peak=harness.peak_of("TPU v5 lite"))


SCAN_OPS = [["%mamba_scan.1 (f32[4,8192,5120] custom-call", 0, 30_000_000],
            ["%mamba_scan (f32[2,8192,5120] custom-call", 40_000_000,
             15_000_000],
            ["%fusion.12 bf16[4,8192,8192] fusion", 60_000_000, 9_000_000],
            ["%mamba_scan_fusion f32[4,8192] fusion", 70_000_000, 1_000_000]]


def test_scan_time_per_token_sums_the_kernel_over_the_traced_prompts():
    assert scan_us.read(_run(SCAN_OPS)) == pytest.approx(
        0.045 / (6 * 8192) * 1e6)


def test_scan_roofline_is_the_bytes_bound_over_the_kernel_time():
    c = arch.counts(CONFIG)
    peak = harness.peak_of("TPU v5 lite")
    least = 26 * 6 * 8192 * 4.0 * (3 * 5120 + 32) / peak["hbm_bytes_per_s"]
    assert c.scan(6, 8192)[0] / peak["bf16_flops_per_s"] < least / 26
    assert scan_roof.read(_run(SCAN_OPS)) == pytest.approx(
        100 * least / 0.045)


@pytest.mark.parametrize("reader", [scan_us, scan_roof])
def test_scan_readers_find_nothing_without_the_kernel(reader):
    assert reader.read(_run(SCAN_OPS[2:])) is None
    assert reader.read(SimpleNamespace(trace=None, traced=[], peak={})) is None
