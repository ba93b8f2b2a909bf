"""Grouped-query attention: training/prefill (blockwise online-softmax) and
single-token decode with a KV cache.

The XLA path is the reference/distribution implementation (what the
multi-pod dry-run lowers); ``attn_impl="pallas"`` switches the hot loops to
the Pallas TPU kernels in ``repro.kernels`` (validated against the same
math in interpret mode).  Prefill never materialises the (S x S) score
matrix: a two-level ``lax.scan`` over query/key chunks runs the standard
online-softmax recurrence, so 32k-token prefill fits activation memory.

KV caches are logical-axis sharded: ``kv_seq`` maps to nothing for normal
decode and to the data axes for long-context decode (sequence-sharded
cache + global logsumexp combine, which GSPMD lowers to the psum pattern).
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental.layout import Layout, with_layout_constraint

from ..dist.api import constrain
from .config import ArchConfig
from .layers import apply_rope, dense_init

Params = dict[str, Any]

NEG_INF = -1e30


def init_attention(key, cfg: ArchConfig, cross: bool = False) -> Params:
    d, hd = cfg.d_model, cfg.head_dim
    dt = cfg.param_dtype
    ks = jax.random.split(key, 4)
    p: Params = {
        "wq": dense_init(ks[0], (d, cfg.n_heads, hd), dt),
        "wk": dense_init(ks[1], (d, cfg.n_kv_heads, hd), dt),
        "wv": dense_init(ks[2], (d, cfg.n_kv_heads, hd), dt),
        "wo": dense_init(ks[3], (cfg.n_heads, hd, d), dt, in_axis=0),
    }
    if cfg.qkv_bias and not cross:
        p["bq"] = jnp.zeros((cfg.n_heads, hd), dt)
        p["bk"] = jnp.zeros((cfg.n_kv_heads, hd), dt)
        p["bv"] = jnp.zeros((cfg.n_kv_heads, hd), dt)
    return p


def _project_q(p: Params, x: jax.Array, cfg: ArchConfig,
               positions: jax.Array | None) -> jax.Array:
    dt = jnp.dtype(cfg.compute_dtype)
    q = jnp.einsum("btd,dnh->btnh", x.astype(dt), p["wq"].astype(dt))
    if "bq" in p:
        q = q + p["bq"].astype(dt)
    if positions is not None and cfg.positions == "rope":
        q = apply_rope(q, positions, cfg.rope_theta)
    return checkpoint_name(constrain(q, "batch", None, "heads", None),
                           "qkv_out")


def _project_kv(p: Params, x: jax.Array, cfg: ArchConfig,
                positions: jax.Array | None) -> tuple[jax.Array, jax.Array]:
    dt = jnp.dtype(cfg.compute_dtype)
    k = jnp.einsum("btd,dnh->btnh", x.astype(dt), p["wk"].astype(dt))
    v = jnp.einsum("btd,dnh->btnh", x.astype(dt), p["wv"].astype(dt))
    if "bk" in p:
        k = k + p["bk"].astype(dt)
        v = v + p["bv"].astype(dt)
    if positions is not None and cfg.positions == "rope":
        k = apply_rope(k, positions, cfg.rope_theta)
    k = checkpoint_name(constrain(k, "batch", None, "kv_heads", None),
                        "qkv_out")
    v = checkpoint_name(constrain(v, "batch", None, "kv_heads", None),
                        "qkv_out")
    return k, v


def _repeat_kv(k: jax.Array, n_heads: int) -> jax.Array:
    """(B,T,KV,hd) -> (B,T,H,hd) by repeating each kv head H/KV times."""
    b, t, kv, hd = k.shape
    if kv == n_heads:
        return k
    rep = n_heads // kv
    return jnp.broadcast_to(k[:, :, :, None, :], (b, t, kv, rep, hd)) \
        .reshape(b, t, n_heads, hd)


def blockwise_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                        causal: bool, q_chunk: int = 512,
                        kv_chunk: int = 1024,
                        q_offset: int = 0) -> jax.Array:
    """Online-softmax attention without materialising (S x S) scores.

    q: (B, Tq, H, hd); k, v: (B, Tk, H, hd) (kv already head-repeated).
    ``q_offset`` shifts query positions for causal masking (prefill
    continuation).  Returns (B, Tq, H, hd) in q.dtype.
    """
    b, tq, h, hd = q.shape
    tk = k.shape[1]
    q_chunk = min(q_chunk, tq)
    kv_chunk = min(kv_chunk, tk)
    n_q, n_k = tq // q_chunk, tk // kv_chunk
    assert tq % q_chunk == 0 and tk % kv_chunk == 0
    scale = hd ** -0.5
    qr = ((q.astype(jnp.float32) * scale).astype(q.dtype)
          .reshape(b, n_q, q_chunk, h, hd))
    kr = k.reshape(b, n_k, kv_chunk, h, hd)
    vr = v.reshape(b, n_k, kv_chunk, h, hd)

    def q_step(_, qi_idx):
        qi, iq = qi_idx  # (b, q_chunk, h, hd), scalar chunk index

        def kv_step(carry, kv_idx):
            acc, m, l = carry
            kj, vj, jk = kv_idx
            s = jnp.einsum("bqhd,bkhd->bhqk", qi, kj,
                           preferred_element_type=jnp.float32)
            if causal:
                qpos = q_offset + iq * q_chunk + jnp.arange(q_chunk)
                kpos = jk * kv_chunk + jnp.arange(kv_chunk)
                mask = qpos[:, None] >= kpos[None, :]
                s = jnp.where(mask[None, None], s, NEG_INF)
            m_new = jnp.maximum(m, s.max(axis=-1))
            alpha = jnp.exp(m - m_new)
            pexp = jnp.exp(s - m_new[..., None])
            l = l * alpha + pexp.sum(axis=-1)
            acc = acc * alpha[..., None] + jnp.einsum(
                "bhqk,bkhd->bhqd", pexp.astype(vj.dtype), vj,
                preferred_element_type=jnp.float32)
            return (acc, m_new, l), None

        acc0 = jnp.zeros((b, h, q_chunk, hd), jnp.float32)
        m0 = jnp.full((b, h, q_chunk), NEG_INF, jnp.float32)
        l0 = jnp.zeros((b, h, q_chunk), jnp.float32)
        (acc, m, l), _ = jax.lax.scan(
            kv_step, (acc0, m0, l0),
            (kr.swapaxes(0, 1), vr.swapaxes(0, 1), jnp.arange(n_k)))
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        return None, out.swapaxes(1, 2)  # (b, q_chunk, h, hd)

    _, chunks = jax.lax.scan(
        q_step, None, (qr.swapaxes(0, 1), jnp.arange(n_q)))
    out = chunks.swapaxes(0, 1).reshape(b, tq, h, hd)
    return out.astype(q.dtype)


def full_attention(p: Params, x: jax.Array, cfg: ArchConfig, *,
                   positions: jax.Array, causal: bool = True,
                   kv_states: jax.Array | None = None,
                   kv_positions: jax.Array | None = None,
                   return_kv: bool = False):
    """Training / prefill attention over full sequences.

    ``kv_states`` switches to cross-attention (keys/values from the encoder
    stream, no RoPE on either side for enc-dec models).  ``return_kv``
    additionally returns the (pre-repeat) keys/values in the cache's
    layout (:func:`to_cache`), for cache fills.
    """
    q = _project_q(p, x, cfg, positions if kv_states is None else None)
    src = x if kv_states is None else kv_states
    if kv_states is None and kv_positions is None:
        kv_positions = positions                      # self-attention RoPE
    k, v = _project_kv(p, src, cfg,
                       kv_positions if kv_states is None else None)
    kr = _repeat_kv(k, cfg.n_heads)
    vr = _repeat_kv(v, cfg.n_heads)

    if cfg.attn_impl == "pallas":
        from ..kernels.flash_attention import ops as fa_ops
        # tuned=None: resolves the cached best launch params when kernel
        # tuning is enabled (repro.tune.kernels.configure; serve.py's
        # --tuned-kernels), hardcoded defaults otherwise
        out = fa_ops.flash_attention(q, kr, vr, causal=causal, tuned=None)
    else:
        out = blockwise_attention(q, kr, vr, causal=causal)
    out = constrain(out, "batch", None, "heads", None)
    dt = jnp.dtype(cfg.compute_dtype)
    res = jnp.einsum("btnh,nhd->btd", out.astype(dt), p["wo"].astype(dt))
    res = constrain(res, "batch", "seq", None)
    if return_kv:
        return res, {"k": to_cache(k), "v": to_cache(v)}
    return res


# -- decode -------------------------------------------------------------------
#
# The KV cache is stored as (S, KV, B, hd) per layer, hd padded to whole
# 128-lane rows; LM.decode_step carries every layer's, stacked on a leading
# axis.  That is the layout a TPU's decode attention reads (head dim in
# lanes, batch rows in sublanes), and with hd padded it is also the default
# layout XLA gives the stored array, so no program copies the cache to
# attend and none needs a layout of its own at its entry or result.  A
# (B, S, KV, 96) cache gets slots in lanes by default, which attention
# cannot read without a copy of the cache.

LANES = 128


def cache_head_dim(hd: int) -> int:
    """Head size as the cache stores it: padded to whole lane rows."""
    return -(-hd // LANES) * LANES


def to_cache(x: jax.Array) -> jax.Array:
    """Keys or values (B, T, KV, hd) -> the cache's (T, KV, B, hd padded)."""
    pad = cache_head_dim(x.shape[-1]) - x.shape[-1]
    x = jnp.transpose(x, (1, 2, 0, 3))
    return jnp.pad(x, ((0, 0),) * 3 + ((0, pad),)) if pad else x


def from_cache(c: jax.Array, hd: int) -> jax.Array:
    """The cache's (S, KV, B, hd padded) -> keys or values (B, S, KV, hd)."""
    return jnp.transpose(c, (2, 0, 1, 3))[..., :hd]


def init_kv_cache(cfg: ArchConfig, batch: int, max_len: int,
                  dtype=None) -> Params:
    dt = dtype or jnp.dtype(cfg.compute_dtype)
    shape = (max_len, cfg.n_kv_heads, batch, cache_head_dim(cfg.head_dim))
    return {"k": jnp.zeros(shape, dt), "v": jnp.zeros(shape, dt)}


def decode_attention(p: Params, x: jax.Array, cache: Params,
                     cfg: ArchConfig, *, pos: jax.Array,
                     layer: jax.Array | None = None,
                     cross: bool = False) -> tuple[jax.Array, Params]:
    """One-token decode. x: (B, 1, D); cache k/v: (S, KV, B, hd padded).

    ``pos`` is the current position (scalar int32): the new KV is written
    at ``pos`` and attention spans positions <= pos.  For cross-attention
    the cache holds precomputed encoder KV and is not updated.

    With ``layer`` the cache is every layer's, stacked on a leading axis:
    the new row is written in place at ``(layer, pos)`` and the layer is
    read from the stack, which XLA fuses into the attention dots, so no
    layer is copied.
    """
    from ..dist.api import current_rules

    b = x.shape[0]
    hd = cfg.head_dim
    q = _project_q(p, x, cfg, None if cross else jnp.full((b, 1), pos))
    rules = current_rules()
    kvseq_axes = tuple(rules.rules.get("kv_seq", ())) if rules else ()
    batch_axes = tuple(rules.rules.get("batch", ())) if rules else ()
    seq_dim = 0 if layer is None else 1
    if kvseq_axes:
        # the sharded path needs shard_map-divisible extents; fall back to
        # the dense path otherwise (rules are hints, not hard partitioning)
        if cache["k"].shape[seq_dim] % rules.axes_size(kvseq_axes) \
                or (batch_axes and b % rules.axes_size(batch_axes)):
            kvseq_axes = ()

    def read(c):
        return c if layer is None else \
            jax.lax.dynamic_index_in_dim(c, layer, keepdims=False)

    if not cross and kvseq_axes:
        # sequence-sharded cache: shard_map'd local update + flash-decode
        # with cross-shard logsumexp combine (see dist.seq_decode), on the
        # layer in (B, S, KV, hd) form.
        from ..dist.seq_decode import seq_decode_attention
        k_new, v_new = _project_kv(p, x, cfg, jnp.full((b, 1), pos))
        out32, ck, cv = seq_decode_attention(
            q[:, 0], k_new[:, 0], v_new[:, 0],
            from_cache(read(cache["k"]), hd), from_cache(read(cache["v"]), hd),
            pos, mesh=rules.mesh, seq_axes=kvseq_axes, batch_axes=batch_axes)
        new = {"k": to_cache(ck), "v": to_cache(cv)}
        cache = new if layer is None else {
            n: jax.lax.dynamic_update_index_in_dim(cache[n], new[n], layer, 0)
            for n in new}
        dt = jnp.dtype(cfg.compute_dtype)
        out = out32.astype(dt)[:, None]                       # (B,1,H,hd)
        res = jnp.einsum("btnh,nhd->btd", out, p["wo"].astype(dt))
        return constrain(res, "batch", None, None), cache
    if not cross:
        k_new, v_new = _project_kv(p, x, cfg, jnp.full((b, 1), pos))
        rows = {"k": to_cache(k_new), "v": to_cache(v_new)}  # (1,KV,B,hdp)
        if layer is None:
            lead, at = (), (pos, 0, 0, 0)
        else:
            lead, at = (None,), (layer, pos, 0, 0, 0)
            rows = {n: r[None] for n, r in rows.items()}
        cache = {n: jax.lax.dynamic_update_slice(
            cache[n], r.astype(cache[n].dtype), at) for n, r in rows.items()}
        # hold the written cache in its stored (default) layout inside the
        # program: left free, XLA relays a 2-row phi3 K stack out (rows
        # before heads) at decode's entry and back at its exit
        cache = {n: with_layout_constraint(c, Layout(tuple(range(c.ndim))))
                 for n, c in cache.items()}
        cache = {n: constrain(c, *lead, "kv_seq", "kv_heads", "batch", None)
                 for n, c in cache.items()}
    k, v = read(cache["k"]), read(cache["v"])              # (S, KV, B, hdp)

    if cfg.attn_impl == "pallas":
        from ..kernels.decode_attention import ops as da_ops
        out = da_ops.decode_attention(q[:, 0], from_cache(k, hd),
                                      from_cache(v, hd),
                                      length=None if cross else pos + 1,
                                      tuned=None)
    else:
        kv_len, n_kv, _, hdp = k.shape
        rep = cfg.n_heads // n_kv
        if rep > 1:                     # (S, KV, B, hdp) -> (S, H, B, hdp)
            k, v = (jnp.repeat(c, rep, axis=1) for c in (k, v))
        # bf16 operands + fp32 accumulation: never materialise an fp32
        # copy of the cache.  q is zero-padded to the cache's head size,
        # so the padded lanes add nothing to the scores.
        qs = (q[:, 0].astype(jnp.float32) * hd ** -0.5).astype(k.dtype)
        qs = jnp.pad(qs, ((0, 0), (0, 0), (0, hdp - hd)))
        s = jnp.einsum("bnh,snbh->bns", qs, k,
                       preferred_element_type=jnp.float32)
        if not cross:
            valid = jnp.arange(kv_len) <= pos
            s = jnp.where(valid, s, NEG_INF)
        w = jax.nn.softmax(s, axis=-1)
        out = jnp.einsum("bns,snbh->bnh", w.astype(v.dtype), v,
                         preferred_element_type=jnp.float32)[..., :hd]
    out = out.astype(jnp.dtype(cfg.compute_dtype))[:, None]  # (B,1,H,hd)
    dt = jnp.dtype(cfg.compute_dtype)
    res = jnp.einsum("btnh,nhd->btd", out, p["wo"].astype(dt))
    return constrain(res, "batch", None, None), cache


def precompute_cross_kv(p: Params, enc: jax.Array, cfg: ArchConfig) -> Params:
    k, v = _project_kv(p, enc, cfg, None)
    return {"k": to_cache(k), "v": to_cache(v)}
