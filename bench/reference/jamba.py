"""Plain float32 reference of the Jamba hybrid (AI21-Jamba2-3B).

The published architecture (HF ``JambaForCausalLM`` with one expert) in
straightforward ``jax.numpy``: token embedding; per layer RMSNorm, the
mixer, residual, RMSNorm, SwiGLU MLP, residual; a final RMSNorm and the
tied output head.  The mixer is attention at the layers ``i`` with ``i %
attn_layer_period == attn_layer_offset`` and a Mamba-1 mixer elsewhere:

* attention: grouped-query, no positional encoding, causal softmax,
  computed over blocks of queries so the scores fit;
* Mamba: ``in_proj`` to (x, z); a causal depthwise conv of width
  ``mamba_d_conv`` over x, then SiLU; ``x_proj`` to (dt, B, C), each
  RMS-normalised; ``dt = softplus(dt_proj(dt) + dt_bias)``; then the
  recurrence token by token, ``h = exp(dt A) h + dt B x`` and ``y = C . h
  + D x`` with ``A = -exp(A_log)``; ``y * silu(z)`` through ``out_proj``.

No cache, no kernels, no batching tricks: one full causal forward over
each prompt followed by its served tokens, every matmul at
``precision="highest"``.  The random weights have zero conv biases and
unit norm scales, so those terms are left out.  It imports nothing of the
program and takes nothing the program made.

Its weights come from the seed, the way the served model's random weights
are defined: ``jax.random.PRNGKey(seed)`` split into embedding, layer and
norm keys; the layer key split into one key per repeating block of
``attn_layer_period`` layers and that into one key per layer; matrices are
truncated normals of standard deviation ``1 / sqrt(fan_in)`` (``fan_in``
the leading axis of each stored matrix), the embedding ``N(0, 0.02)``,
the conv taps ``N(0, 1 / d_conv)``, ``dt_bias`` the inverse softplus of a
log-uniform draw in [1e-3, 1e-1], ``A_log = log(1 .. d_state)`` and ``D =
1`` in float32; every other weight is rounded to bfloat16 as served.
Weights are made layer by layer inside the forward, so the reference
never holds the whole model.

``control=True`` is the reference one precision step down, the step that
would tempt a later change: computed in float8 (e4m3).  Every weight
matrix is rounded to float8 with one scale per output channel, and every
activation entering a weight matmul with one scale per token; products
still sum in float32, and attention's scores, the conv, the scan and the
norms stay float32.
"""

from __future__ import annotations

import functools
import math

import numpy as np

import jax
import jax.numpy as jnp

F8_MAX = 448.0                          # largest finite float8_e4m3fn
Q_BLOCK = 256                           # attention queries per block


def _dims(config: dict) -> dict:
    d, heads = config["hidden_size"], config["num_attention_heads"]
    return {"d": d, "L": config["num_hidden_layers"], "H": heads,
            "KV": config["num_key_value_heads"], "hd": d // heads,
            "ff": config["intermediate_size"], "V": config["vocab_size"],
            "period": config["attn_layer_period"],
            "offset": config["attn_layer_offset"],
            "di": config["mamba_expand"] * d, "S": config["mamba_d_state"],
            "K": config["mamba_d_conv"], "r": config["mamba_dt_rank"],
            "eps": float(config["rms_norm_eps"])}


def _served(w):
    """float32 values of the bfloat16 weight as served."""
    return w.astype(jnp.bfloat16).astype(jnp.float32)


def _fp8(w, in_axes):
    """Round to float8 e4m3 with one scale per output channel."""
    scale = jnp.max(jnp.abs(w), axis=in_axes, keepdims=True) / F8_MAX
    scale = jnp.where(scale > 0, scale, 1.0)
    return (w / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _mm(control, spec, x, w):
    """A weight matmul; in the control its activation is float8 too."""
    if control:
        x = _fp8(x, (-1,))
    return jnp.einsum(spec, x, w)


def _dense(key, shape, control, in_axes=(0,)):
    w = _served(shape[0] ** -0.5
                * jax.random.truncated_normal(key, -2.0, 2.0, shape))
    return _fp8(w, in_axes) if control else w


def _mlp_weights(key, m, control):
    km = jax.random.split(jax.random.split(key, 4)[3], 3)
    return {"w_in": _dense(km[0], (m["d"], m["ff"]), control),
            "w_out": _dense(km[1], (m["ff"], m["d"]), control),
            "w_gate": _dense(km[2], (m["d"], m["ff"]), control)}


def _attn_weights(key, m, control):
    d, hd = m["d"], m["hd"]
    ka = jax.random.split(jax.random.split(key, 4)[0], 4)
    return {"wq": _dense(ka[0], (d, m["H"], hd), control),
            "wk": _dense(ka[1], (d, m["KV"], hd), control),
            "wv": _dense(ka[2], (d, m["KV"], hd), control),
            "wo": _dense(ka[3], (m["H"], hd, d), control, (0, 1))}


def _mamba_weights(key, m, control):
    d, di, s, r, k = m["d"], m["di"], m["S"], m["r"], m["K"]
    ks = jax.random.split(jax.random.split(key, 4)[0], 6)
    u = jax.random.uniform(ks[0], (di,))
    dt = jnp.exp(u * (jnp.log(0.1) - jnp.log(1e-3)) + jnp.log(1e-3))
    return {"dt_bias": _served(dt + jnp.log(-jnp.expm1(-dt))),
            "in_proj": _dense(ks[1], (d, 2 * di), control),
            "conv_w": _served(jax.random.normal(ks[2], (k, di)) * k ** -0.5),
            "x_proj": _dense(ks[3], (di, r + 2 * s), control),
            "dt_proj": _dense(ks[4], (r, di), control),
            "A_log": jnp.log(jnp.arange(1, s + 1, dtype=jnp.float32)
                             )[:, None] * jnp.ones((1, di), jnp.float32),
            "D": jnp.ones((di,), jnp.float32),
            "out_proj": _dense(ks[5], (di, d), control)}


def _rms(x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def _mlp(x, w, m, control):
    h = _rms(x, m["eps"])
    g = jax.nn.silu(_mm(control, "btd,df->btf", h, w["w_gate"])) \
        * _mm(control, "btd,df->btf", h, w["w_in"])
    return x + _mm(control, "btf,fd->btd", g, w["w_out"])


def _attention(x, w, m, control):
    b, t, _ = x.shape
    h = _rms(x, m["eps"])
    q = _mm(control, "btd,dnh->btnh", h, w["wq"]) / math.sqrt(m["hd"])
    k = _mm(control, "btd,dnh->btnh", h, w["wk"])
    v = _mm(control, "btd,dnh->btnh", h, w["wv"])
    rep = m["H"] // m["KV"]
    k, v = jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)
    n_blocks = -(-t // Q_BLOCK)
    qp = jnp.pad(q, ((0, 0), (0, n_blocks * Q_BLOCK - t), (0, 0), (0, 0)))

    def block(i):
        qi = jax.lax.dynamic_slice_in_dim(qp, i * Q_BLOCK, Q_BLOCK, axis=1)
        s = jnp.einsum("bqnh,bknh->bnqk", qi, k)
        pos = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        causal = pos[:, None] >= jnp.arange(t)[None, :]
        p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        return jnp.einsum("bnqk,bknh->bqnh", p, v)

    o = jax.lax.map(block, jnp.arange(n_blocks))       # (blocks, b, Q, n, hd)
    o = jnp.moveaxis(o, 0, 1).reshape(b, n_blocks * Q_BLOCK, m["H"],
                                      m["hd"])[:, :t]
    return x + _mm(control, "btk,kd->btd", o.reshape(b, t, -1),
                   w["wo"].reshape(m["H"] * m["hd"], -1))


def _mamba(x, w, m, control):
    b, t, _ = x.shape
    di, s, r, k = m["di"], m["S"], m["r"], m["K"]
    h = _rms(x, m["eps"])
    xz = _mm(control, "btd,de->bte", h, w["in_proj"])
    xs, z = xz[..., :di], xz[..., di:]
    xp = jnp.pad(xs, ((0, 0), (k - 1, 0), (0, 0)))     # causal: zeros before
    xc = jax.nn.silu(sum(xp[:, i:i + t] * w["conv_w"][i] for i in range(k)))
    dbc = _mm(control, "bte,ef->btf", xc, w["x_proj"])
    dt = _rms(dbc[..., :r], m["eps"])
    bs = _rms(dbc[..., r:r + s], m["eps"])
    cs = _rms(dbc[..., r + s:], m["eps"])
    delta = jax.nn.softplus(_mm(control, "btr,re->bte", dt, w["dt_proj"])
                            + w["dt_bias"])

    a = -jnp.exp(w["A_log"])                            # (S, d_inner)

    # the recurrence, token by token; the state is (b, d_state, d_inner)
    def step(state, inp):
        d_t, x_t, b_t, c_t = inp                       # (b,di) (b,di) (b,S) (b,S)
        state = jnp.exp(d_t[:, None, :] * a) * state \
            + b_t[:, :, None] * (d_t * x_t)[:, None, :]
        return state, jnp.sum(state * c_t[:, :, None], axis=1) + w["D"] * x_t

    _, ys = jax.lax.scan(step, jnp.zeros((b, s, di), jnp.float32),
                         (delta.swapaxes(0, 1), xc.swapaxes(0, 1),
                          bs.swapaxes(0, 1), cs.swapaxes(0, 1)))
    y = ys.swapaxes(0, 1) * jax.nn.silu(z)
    return x + _mm(control, "bte,ed->btd", y, w["out_proj"])


def _runs(m: dict) -> list[tuple[str, list[int]]]:
    """The layers as runs of one kind, in order: [(kind, [layer, ...])]."""
    out: list[tuple[str, list[int]]] = []
    for i in range(m["L"]):
        kind = "attn" if i % m["period"] == m["offset"] else "mamba"
        if out and out[-1][0] == kind:
            out[-1][1].append(i)
        else:
            out.append((kind, [i]))
    return out


@functools.partial(jax.jit, static_argnames=("m_items", "first", "control"))
def _logits(seed_key, tokens, *, m_items, first: int, control: bool):
    """Logits (B, T - first, V) at positions ``first ..`` of ``tokens``."""
    m = dict(m_items)
    k_emb, k_layers, _ = jax.random.split(seed_key, 3)
    group_keys = jax.random.split(k_layers, m["L"] // m["period"])
    layer_keys = jnp.concatenate([jax.random.split(g, m["period"])
                                  for g in group_keys])
    table = _served(jax.random.normal(jax.random.split(k_emb, 2)[0],
                                      (m["V"], m["d"])) * 0.02)
    if control:
        table = _fp8(table, (1,))
    x = table[tokens]
    mixer = {"attn": (_attention, _attn_weights),
             "mamba": (_mamba, _mamba_weights)}
    for kind, layers in _runs(m):
        apply, weights = mixer[kind]

        def body(x, key, apply=apply, weights=weights):
            x = apply(x, weights(key, m, control), m, control)
            return _mlp(x, _mlp_weights(key, m, control), m, control), None

        x, _ = jax.lax.scan(body, x, layer_keys[jnp.asarray(layers)])
    h = _rms(x[:, first:], m["eps"])
    return _mm(control, "btd,dv->btv", h, table.T)


def logits(config: dict, seed: int, tokens: np.ndarray, first: int, *,
           control: bool = False, block_rows: int = 8,
           block_bytes: float = 8e9) -> np.ndarray:
    """Reference logits at positions ``first ..`` of every row of
    ``tokens`` (rows, T), float32 on the host, computed in blocks of at
    most ``block_rows`` rows whose float32 activations (the scan's
    operands and output, the MLP's hidden, the Mamba projections) stay
    under ``block_bytes``; the last block is padded, so one shape
    compiles."""
    m = _dims(config)
    rows, t = tokens.shape
    per_row = 4 * t * (7 * m["di"] + 3 * m["ff"])
    block = int(max(1, min(block_rows, block_bytes // per_row)))
    padded = -(-rows // block) * block
    toks = np.zeros((padded, t), np.int32)
    toks[:rows] = tokens
    key = jax.random.PRNGKey(seed)
    out = []
    with jax.default_matmul_precision("highest"):
        for lo in range(0, padded, block):
            out.append(np.asarray(_logits(
                key, jnp.asarray(toks[lo:lo + block]),
                m_items=tuple(sorted(m.items())), first=first,
                control=control)))
    return np.concatenate(out)[:rows]


def readings(config: dict, seed: int, prompts: np.ndarray,
             served: np.ndarray, prefill_logits: np.ndarray, *,
             control: bool = False, block_rows: int = 8) -> dict:
    """The two numbers ``correct`` compares, for rows served from
    ``prompts`` (rows, P): the greedy ``served`` tokens (rows, G) and the
    last-position ``prefill_logits`` (rows, V).

    ``token_gap``: the widest gap by which a served token's reference
    logit lies below the reference's best at that position.
    ``prefill_logit_err``: the largest gap between served and reference
    prefill logits, over the largest reference logit of that row.

    With ``control=True`` the served side is replaced by the reference
    one precision down, over the same prompts and tokens: its first
    choice at each position, and its own prefill logits."""
    p = prompts.shape[1]
    seq = np.concatenate([prompts, served[:, :-1]], axis=1)
    ref = logits(config, seed, seq, p - 1, block_rows=block_rows)
    if control:
        low = logits(config, seed, seq, p - 1, control=True,
                     block_rows=block_rows)
        chosen, first = low.argmax(-1), low[:, 0]
    else:
        chosen, first = served, prefill_logits
    best = ref.max(-1)
    got = np.take_along_axis(ref, chosen[..., None], -1)[..., 0]
    scale = np.abs(ref[:, 0]).max(-1)
    return {"token_gap": float((best - got).max()),
            "prefill_logit_err": float(
                (np.abs(first - ref[:, 0]).max(-1) / scale).max())}
