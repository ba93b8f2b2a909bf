"""The plain reference against the model's own XLA path, at a small size.

The reference makes its weights from the seed itself; they must be the
served model's weights bit for bit.  With those weights, its float32
logits must match the model's prefill and cached decode computed in
float32, position by position.
"""

from __future__ import annotations

import json
from dataclasses import replace

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from bench import harness
from bench.reference import dense_decoder as ref

arch = harness._module("models", "dense_decoder")

SMALL = {"hidden_size": 64, "intermediate_size": 128, "num_hidden_layers": 2,
         "num_attention_heads": 4, "head_dim": 16, "vocab_size": 256}
WORKLOADS = ["qwen2.5-3b", "phi3-mini-3.8b"]
SEED = 2 ** 31 + 12345


def small_config(workload: str) -> dict:
    config = json.loads((harness.BENCH / "configs" / f"{workload}.json")
                        .read_text())
    kv = 2 if config["num_key_value_heads"] < config["num_attention_heads"] \
        else 4
    return {**config, **SMALL, "num_key_value_heads": kv}


@pytest.mark.parametrize("workload", WORKLOADS)
def test_weights_are_the_served_weights(workload):
    from repro.launch.serve import serving_model
    config = small_config(workload)
    params = jax.jit(serving_model(arch.arch_config(config)).init)(
        jax.random.PRNGKey(SEED))
    m = ref._dims(config)
    k_emb, k_layers, _ = jax.random.split(jax.random.PRNGKey(SEED), 3)
    layer = params["layers"]["slot0"]
    for i, key in enumerate(jax.random.split(k_layers, m["L"])):
        mine = ref._layer_weights(key, m, control=False)
        served = {**{k: layer["mixer"][k][i] for k in ("wq", "wk", "wv", "wo")},
                  **{k: layer["channel"][k][i]
                     for k in ("w_in", "w_out", "w_gate")}}
        for name, w in mine.items():
            np.testing.assert_array_equal(
                np.asarray(w), np.asarray(served[name], np.float32), name)
    k_tok, k_head = jax.random.split(k_emb, 2)
    table = ref._served(jax.random.normal(k_tok, (m["V"], m["d"])) * 0.02)
    np.testing.assert_array_equal(
        np.asarray(table), np.asarray(params["embed"]["tokens"], np.float32))
    if not m["tied"]:
        np.testing.assert_array_equal(
            np.asarray(ref._dense(k_head, (m["d"], m["V"]), False)),
            np.asarray(params["embed"]["lm_head"], np.float32))


@pytest.mark.parametrize("workload", WORKLOADS)
def test_reference_logits_match_the_model_in_float32(workload):
    from repro.models import build_model
    config = small_config(workload)
    cfg = replace(arch.arch_config(config), param_dtype="bfloat16",
                  compute_dtype="float32")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(SEED))
    rng = np.random.default_rng(0)
    prompt, gen = 8, 5
    seq = rng.integers(0, SMALL["vocab_size"], (2, prompt + gen - 1),
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


def test_control_rounds_every_weight_to_float8():
    w = jnp.asarray(np.random.default_rng(1).standard_normal((64, 8)),
                    jnp.float32)
    q = ref._fp8(w, (0,))
    scale = np.abs(np.asarray(w)).max(0) / ref.F8_MAX
    steps = np.asarray(q) / scale
    np.testing.assert_allclose(            # on the float8 grid
        steps, np.asarray(jnp.asarray(steps).astype(jnp.float8_e4m3fn)
                          .astype(jnp.float32)), rtol=1e-5)
    assert 0 < np.abs(np.asarray(q) - np.asarray(w)).max() \
        <= np.abs(np.asarray(w)).max() / 16
