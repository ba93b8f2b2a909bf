"""Per-architecture smoke tests (reduced configs) + semantic checks.

Every assigned arch: one forward/train step on CPU asserting output shapes
and finite values; prefill->decode consistency against the full forward
(exact for SSM/attention state reconstruction).
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.core.hetero import DeviceGroup
from repro.launch.serve import _stream_step_builder
from repro.models import build_model
from repro.models.layers import init_mlp, apply_mlp
from repro.models.moe import apply_moe, init_moe

KEY = jax.random.PRNGKey(0)


def _batch_for(cfg, b=2, t=32, seed=0):
    rng = np.random.default_rng(seed)
    if cfg.encdec:
        return {
            "frame_embeds": jnp.asarray(
                rng.standard_normal((b, t, cfg.d_model)) * 0.02, jnp.float32),
            "tokens": jnp.asarray(rng.integers(0, cfg.vocab_size,
                                               (b, cfg.decoder_len)),
                                  jnp.int32),
            "labels": jnp.asarray(rng.integers(0, cfg.vocab_size,
                                               (b, cfg.decoder_len)),
                                  jnp.int32),
        }
    out = {"tokens": jnp.asarray(rng.integers(0, cfg.vocab_size, (b, t)),
                                 jnp.int32),
           "labels": jnp.asarray(rng.integers(0, cfg.vocab_size, (b, t)),
                                 jnp.int32)}
    if cfg.frontend == "stub_patches":
        out["patch_embeds"] = jnp.asarray(
            rng.standard_normal((b, cfg.n_patches, cfg.d_model)) * 0.02,
            jnp.float32)
    return out


@pytest.mark.parametrize("name", configs.ARCH_NAMES)
def test_arch_smoke_loss_and_grad_step(name):
    cfg = configs.get(name).smoke()
    model = build_model(cfg)
    params = model.init(KEY)
    batch = _batch_for(cfg)

    loss, metrics = jax.jit(model.loss)(params, batch)
    assert np.isfinite(float(loss)), f"{name}: non-finite loss"
    assert 2.0 < float(loss) < 12.0

    grads = jax.jit(jax.grad(lambda p, b: model.loss(p, b)[0]))(params, batch)
    gn = sum(float(jnp.sum(jnp.abs(g.astype(jnp.float32))))
             for g in jax.tree.leaves(grads))
    assert np.isfinite(gn) and gn > 0, f"{name}: bad grads"


@pytest.mark.parametrize("name", configs.ARCH_NAMES)
def test_arch_decode_step_shapes(name):
    cfg = configs.get(name).smoke()
    model = build_model(cfg)
    params = model.init(KEY)
    b, cache_len = 2, 64
    if cfg.encdec:
        state = model.init_decode_state(b, cache_len, cross_len=16)
        frames = _batch_for(cfg, b=b, t=16)["frame_embeds"]
        state = model.prefill_cross(params, state, frames)
    else:
        state = model.init_decode_state(b, cache_len)
    tok = jnp.zeros((b, 1), jnp.int32)
    logits, state2 = jax.jit(model.decode_step)(params, state, tok,
                                                jnp.int32(3))
    assert logits.shape == (b, 1, cfg.vocab_size)
    assert np.isfinite(np.asarray(logits, np.float32)).all()
    assert jax.tree.structure(state) == jax.tree.structure(state2)


@pytest.mark.parametrize("name,served", [
    pytest.param(name, False, id=name)
    for name in ("qwen2.5-3b", "phi3-mini-3.8b", "rwkv6-1.6b",
                 "jamba-v0.1-52b", "phi3.5-moe-42b-a6.6b", "jamba2-3b")
] + [pytest.param(name, True, id=f"{name}-served")
     for name in ("phi3-mini-3.8b", "jamba2-3b")])
def test_prefill_then_decode_matches_forward(name, served):
    """logits(prefill(x[:n]) -> decode x[n], x[n+1], ...) == the
    teacher-forced forward at every position, so each decode step's
    carried write of its cache row (or recurrent state) is checked.

    ``served`` runs the serving step builder's own prefill and donated
    decode on a chunk, greedily, and checks its tokens and prefill
    logits against the forward over the prompt and what it served.  MoE
    capacity is raised so no token drops: capacity-based routing
    legitimately differs between a full pass (overflow drops) and
    single-token decode (never overflows) — the standard train/serve
    asymmetry, not a bug."""
    cfg = dataclasses.replace(configs.get(name).smoke(),
                              param_dtype="float32",
                              compute_dtype="float32")
    if cfg.moe is not None:
        cfg = dataclasses.replace(
            cfg, moe=dataclasses.replace(cfg.moe, capacity_factor=8.0))
    model = build_model(cfg)
    params = model.init(KEY)
    b, t, steps = 2, 16, 4
    batch = _batch_for(cfg, b=b, t=t)
    tokens = batch["tokens"]
    n = t - steps
    if served:
        group = DeviceGroup("all", jax.devices()[:1])
        fn = _stream_step_builder(model, prompt_len=n, gen=steps + 1,
                                  seed=0)(group)
        out = fn({"tokens": np.asarray(tokens[:, :n])})
        got = np.asarray(out["tokens"])
        tokens = jnp.concatenate([tokens[:, :n], got[:, :-1]], axis=1)
        batch = {**batch, "tokens": tokens}

    # teacher-forced logits for every position via loss-path backbone
    x, positions, _, _ = model.embed_inputs(params, batch)
    h, _ = model.backbone(params, x, positions)
    head = (params["embed"]["tokens"].T if cfg.tie_embeddings
            else params["embed"]["lm_head"])
    full_logits = h.astype(jnp.float32) @ head.astype(jnp.float32)
    if served:
        np.testing.assert_allclose(np.asarray(out["logits"]),
                                   np.asarray(full_logits[:, n - 1]),
                                   atol=2e-3, rtol=2e-3)
        np.testing.assert_array_equal(
            got, np.asarray(jnp.argmax(full_logits[:, n - 1:], axis=-1)))
        return

    logits_p, state = model.prefill(params, tokens[:, :n], max_len=t + 4)
    np.testing.assert_allclose(np.asarray(logits_p[:, 0]),
                               np.asarray(full_logits[:, n - 1]),
                               atol=2e-3, rtol=2e-3)
    for i in range(n, t):
        logits_d, state = model.decode_step(params, state, tokens[:, i:i + 1],
                                            jnp.int32(i))
        np.testing.assert_allclose(np.asarray(logits_d[:, 0]),
                                   np.asarray(full_logits[:, i]),
                                   atol=2e-3, rtol=2e-3,
                                   err_msg=f"decode at position {i}")


def test_moe_matches_dense_mlp_when_single_expert():
    """E=1, k=1, ample capacity -> MoE == plain MLP with that expert."""
    cfg = dataclasses.replace(
        configs.get("phi3.5-moe-42b-a6.6b").smoke(),
        param_dtype="float32", compute_dtype="float32")
    cfg = dataclasses.replace(
        cfg, moe=dataclasses.replace(cfg.moe, n_experts=1, top_k=1,
                                     capacity_factor=2.0))
    p = init_moe(jax.random.PRNGKey(1), cfg)
    x = jnp.asarray(np.random.default_rng(0).standard_normal((2, 8,
                                                              cfg.d_model)),
                    jnp.float32) * 0.5
    out, aux = apply_moe(p, x, cfg)
    mlp_p = {"w_in": p["w_in"][0], "w_out": p["w_out"][0],
             "w_gate": p["w_gate"][0]}
    dcfg = dataclasses.replace(cfg, d_ff=cfg.moe.d_expert)
    want = apply_mlp(mlp_p, x, dcfg)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=1e-5, rtol=1e-5)
    assert float(aux) == pytest.approx(1.0, abs=1e-5)  # E * f * p = 1


def test_moe_capacity_drops_overflow_tokens():
    cfg = dataclasses.replace(
        configs.get("phi3.5-moe-42b-a6.6b").smoke(),
        param_dtype="float32", compute_dtype="float32")
    # capacity_factor tiny -> most tokens dropped -> output ~0 for them
    cfg = dataclasses.replace(
        cfg, moe=dataclasses.replace(cfg.moe, capacity_factor=0.01))
    p = init_moe(jax.random.PRNGKey(1), cfg)
    x = jnp.ones((1, 64, cfg.d_model), jnp.float32)
    out, _ = apply_moe(p, x, cfg)
    # capacity rounds up to 4 slots/expert; most rows fall through to 0
    norms = jnp.linalg.norm(out[0], axis=-1)
    assert float((norms < 1e-6).mean()) > 0.3


def test_param_counts_match_published_sizes():
    expected = {
        "rwkv6-1.6b": (1.4e9, 1.8e9),
        "internvl2-76b": (68e9, 72e9),          # backbone of the 76B VLM
        "nemotron-4-340b": (330e9, 350e9),
        "phi4-mini-3.8b": (3.6e9, 4.1e9),
        "phi3-mini-3.8b": (3.6e9, 4.0e9),
        "qwen2.5-3b": (2.8e9, 3.3e9),
        "qwen2-moe-a2.7b": (13e9, 15e9),
        "phi3.5-moe-42b-a6.6b": (40e9, 43e9),
        "jamba-v0.1-52b": (50e9, 53e9),
        "jamba2-3b": (2.9e9, 3.2e9),
        "whisper-base": (0.06e9, 0.09e9),
    }
    actives = {
        "qwen2-moe-a2.7b": (2.4e9, 3.1e9),
        "phi3.5-moe-42b-a6.6b": (6.0e9, 7.0e9),
        "jamba-v0.1-52b": (11e9, 13e9),
    }
    for name, (lo, hi) in expected.items():
        n = configs.get(name).param_count()
        assert lo <= n <= hi, f"{name}: {n/1e9:.2f}B outside [{lo},{hi}]"
    for name, (lo, hi) in actives.items():
        n = configs.get(name).active_param_count()
        assert lo <= n <= hi, f"{name} active: {n/1e9:.2f}B"


def test_mamba_inner_norms_add_their_parameters():
    """A Jamba Mamba layer by hand: in_proj, conv, x_proj, dt_proj and
    its bias, A_log, D, out_proj, and the RMSNorms on dt, B and C
    (dt_rank + 2 * d_state scales)."""
    for name, d, dt_rank, norms in (("jamba-v0.1-52b", 4096, 256, 288),
                                    ("jamba2-3b", 2560, 160, 192)):
        cfg = configs.get(name)
        d_in = 2 * d
        layer = (d * 2 * d_in + 4 * d_in + d_in * (dt_rank + 32)
                 + dt_rank * d_in + d_in + d_in * 16 + d_in + d_in * d)
        i = cfg.layer_kinds.index("mamba")
        assert dict(cfg.param_breakdown())[f"mamba[{i}]"] == layer + norms
        params = jax.eval_shape(build_model(cfg.smoke()).init, KEY)
        mixer = params["layers"]["slot0"]["mixer"]
        assert {k: v.shape[1:] for k, v in mixer.items()
                if k.endswith("_norm")} == {
            "dt_norm": (16,), "b_norm": (8,), "c_norm": (8,)}


def test_group_pattern_jamba():
    cfg = configs.get("jamba-v0.1-52b")
    assert len(cfg.group_pattern) == 8
    assert cfg.group_pattern[4] == "attn"
    assert cfg.n_groups == 4
    assert sum(1 for k in cfg.layer_kinds if k == "attn") == 4
    assert sum(cfg.moe_layer_mask()) == 16


def test_long_context_applicability():
    from repro.launch import shapes
    long = shapes.SHAPE_CELLS["long_500k"]
    runs = [n for n in configs.ARCH_NAMES
            if shapes.applicable(configs.get(n), long)[0]]
    assert sorted(runs) == ["jamba-v0.1-52b", "jamba2-3b", "rwkv6-1.6b"]
