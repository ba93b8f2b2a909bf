"""Plain float32 reference of a dense decoder (Qwen2.5, Phi-3-mini).

The published architecture in straightforward ``jax.numpy``: token
embedding; per layer RMSNorm, grouped-query attention with rotary
positions (rotate-half form, base ``rope_theta``), causal softmax, output
projection, residual, RMSNorm, SwiGLU MLP, residual; a final RMSNorm and
the output head (the embedding matrix when tied).  No cache, no kernels,
no batching tricks: one full causal forward over each prompt followed by
its served tokens, every matmul at ``precision="highest"``.  The random
weights have zero q/k/v biases and unit norm scales, so those terms are
left out.  It imports nothing of the program and takes nothing the
program made.

Its weights come from the seed, the way the served model's random weights
are defined: ``jax.random.PRNGKey(seed)`` split into embedding, layer and
norm keys, one key per layer, truncated normals of standard deviation
``1 / sqrt(fan_in)`` (``fan_in`` being the leading axis of each stored
matrix), embeddings ``N(0, 0.02)``, zero biases, unit norm scales, all
rounded to bfloat16 as served.  Weights are made layer by layer inside the
forward, so the reference never holds the whole model.

``control=True`` is the reference one precision step down, the step that
would tempt a later change: computed in float8 (e4m3).  Every weight
matrix is rounded to float8 with one scale per output channel, and every
activation entering a weight matmul with one scale per token; products
still sum in float32, and attention and the norms stay float32.
"""

from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp

F8_MAX = 448.0                          # largest finite float8_e4m3fn


def _dims(config: dict) -> dict:
    return {"d": config["hidden_size"], "L": config["num_hidden_layers"],
            "H": config["num_attention_heads"],
            "KV": config["num_key_value_heads"], "hd": config["head_dim"],
            "ff": config["intermediate_size"], "V": config["vocab_size"],
            "tied": config["tie_word_embeddings"],
            "theta": float(config["rope_theta"]),
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


def _layer_weights(key, m: dict, control: bool) -> dict:
    d, hd, ff = m["d"], m["hd"], m["ff"]
    k_attn, _, _, k_mlp = jax.random.split(jax.random.split(key, 1)[0], 4)
    ka = jax.random.split(k_attn, 4)
    km = jax.random.split(k_mlp, 3)
    return {"wq": _dense(ka[0], (d, m["H"], hd), control),
            "wk": _dense(ka[1], (d, m["KV"], hd), control),
            "wv": _dense(ka[2], (d, m["KV"], hd), control),
            "wo": _dense(ka[3], (m["H"], hd, d), control, (0, 1)),
            "w_in": _dense(km[0], (d, ff), control),
            "w_out": _dense(km[1], (ff, d), control),
            "w_gate": _dense(km[2], (d, ff), control)}


def _rms(x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def _rope(x, theta):
    """x: (B, T, heads, hd), rotated by position along T."""
    half = x.shape[-1] // 2
    freqs = 1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _layer(x, w, m, control):
    t = x.shape[1]
    h = _rms(x, m["eps"])
    q = _rope(_mm(control, "btd,dnh->btnh", h, w["wq"]), m["theta"])
    k = _rope(_mm(control, "btd,dnh->btnh", h, w["wk"]), m["theta"])
    v = _mm(control, "btd,dnh->btnh", h, w["wv"])
    rep = m["H"] // m["KV"]
    k, v = jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)
    s = jnp.einsum("bqnh,bknh->bnqk", q, k) / jnp.sqrt(float(m["hd"]))
    causal = jnp.tril(jnp.ones((t, t), bool))
    p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
    o = jnp.einsum("bnqk,bknh->bqnh", p, v)
    b, t, n, hd = o.shape
    x = x + _mm(control, "btk,kd->btd", o.reshape(b, t, n * hd),
                w["wo"].reshape(n * hd, -1))
    h = _rms(x, m["eps"])
    g = jax.nn.silu(_mm(control, "btd,df->btf", h, w["w_gate"])) \
        * _mm(control, "btd,df->btf", h, w["w_in"])
    return x + _mm(control, "btf,fd->btd", g, w["w_out"])


@functools.partial(jax.jit, static_argnames=("m_items", "first", "control"))
def _logits(seed_key, tokens, *, m_items, first: int, control: bool):
    """Logits (B, T - first, V) at positions ``first ..`` of ``tokens``."""
    m = dict(m_items)
    k_emb, k_layers, _ = jax.random.split(seed_key, 3)
    k_tok, k_head = jax.random.split(k_emb, 2)
    table = _served(jax.random.normal(k_tok, (m["V"], m["d"])) * 0.02)
    if control:
        table = _fp8(table, (1,))
    x = table[tokens]

    def body(x, key):
        return _layer(x, _layer_weights(key, m, control), m, control), None

    x, _ = jax.lax.scan(body, x, jax.random.split(k_layers, m["L"]))
    h = _rms(x[:, first:], m["eps"])
    head = table.T if m["tied"] else _dense(k_head, (m["d"], m["V"]), control)
    return _mm(control, "btd,dv->btv", h, head)


def logits(config: dict, seed: int, tokens: np.ndarray, first: int, *,
           control: bool = False, block_rows: int = 8,
           block_bytes: float = 4e9) -> np.ndarray:
    """Reference logits at positions ``first ..`` of every row of
    ``tokens`` (rows, T), float32 on the host, computed in blocks of at
    most ``block_rows`` rows whose attention scores stay under
    ``block_bytes``; the last block is padded, so one shape compiles."""
    m = _dims(config)
    rows, t = tokens.shape
    per_row = m["H"] * t * t * 4 * 3
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
