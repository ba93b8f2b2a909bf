"""Flash-decode Pallas TPU kernel: one query token vs a long KV cache.

Grid (B, KV, splits, S/splits/bs): for each (batch, kv-head) the cache
is partitioned into ``splits`` independent segments; each segment
streams its blocks through VMEM, carrying the online-softmax state for
the ``rep = H/KV`` query heads that share this kv head, and emits an
*unnormalised* partial (acc, m, l).  The partials are combined outside
the kernel with one logsumexp rescale — the standard split-KV decode
trick: more segments expose more grid parallelism on a cache too long
for one sequential sweep, at the cost of a (tiny) combine.  The grouped
layout makes the score matmul (rep x hd) @ (hd x bs) — MXU-shaped when
rep is padded to 8 sublanes — and reads each cache block exactly once
(the HBM roofline for decode).  ``splits`` and ``block_s`` are both
tuned (``repro.tune.kernels``).

A ``length`` scalar (SMEM) masks positions >= length, so one compiled
kernel serves any fill level of a fixed-capacity cache.

TPU tiling: the (B, S, KV, hd) cache is read through its free
(B, S, KV*hd) view, one kv head per ``hd``-wide lane block, so a cache
block is (block_s, hd) on the (8, 128) tile grid.  That needs ``hd`` a
multiple of 128 (or KV == 1) when compiled.  Row statistics m and l are
(rep, 1) columns for the same reason.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import grid_compiler_params, largest_aligned_divisor

NEG_INF = -1e30


def _kernel(len_ref, q_ref, k_ref, v_ref, acc_out_ref, m_out_ref, l_out_ref,
            acc_ref, m_ref, l_ref, *, scale, n_s, block_s, seg):
    sp = pl.program_id(2)
    js = pl.program_id(3)

    @pl.when(js == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0, 0].astype(jnp.float32) * scale       # (rep, hd)
    k = k_ref[0].astype(jnp.float32)                  # (bs, hd)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))  # (rep, bs)
    pos = (sp * seg + js * block_s
           + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1))
    s = jnp.where(pos < len_ref[0], s, NEG_INF)
    m_prev = m_ref[...]                               # (rep, 1)
    m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)
    l_ref[...] = l_ref[...] * alpha + p.sum(axis=1, keepdims=True)
    v = v_ref[0].astype(jnp.float32)                  # (bs, hd)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot(p, v)
    m_ref[...] = m_new

    @pl.when(js == n_s - 1)
    def _final():
        acc_out_ref[0, 0, 0] = acc_ref[...]
        m_out_ref[0, 0, 0] = m_ref[...]
        l_out_ref[0, 0, 0] = l_ref[...]


def decode_attention_kernel(q, k, v, length, *, block_s: int = 512,
                            splits: int = 1, dims: str = "parallel",
                            interpret: bool = False):
    """q: (B, KV, rep, hd); k/v: (B, S, KV, hd); length: (1,) int32.

    Returns (B, KV, rep, hd) fp32.
    """
    b, kv, rep, hd = q.shape
    s_len = k.shape[1]
    k = k.reshape(b, s_len, kv * hd)                  # free lane view
    v = v.reshape(b, s_len, kv * hd)
    splits = largest_aligned_divisor(s_len, max(int(splits), 1))
    seg = s_len // splits
    block_s = largest_aligned_divisor(seg, block_s, align=8)
    n_s = seg // block_s
    kernel = functools.partial(_kernel, scale=hd ** -0.5, n_s=n_s,
                               block_s=block_s, seg=seg)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,                 # `length` lands in SMEM
        grid=(b, kv, splits, n_s),
        in_specs=[
            pl.BlockSpec((1, 1, rep, hd),
                         lambda b_, g, sp, j, *_: (b_, g, 0, 0)),
            pl.BlockSpec((1, block_s, hd),
                         lambda b_, g, sp, j, *_: (b_, sp * n_s + j, g)),
            pl.BlockSpec((1, block_s, hd),
                         lambda b_, g, sp, j, *_: (b_, sp * n_s + j, g)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, 1, rep, hd),
                         lambda b_, g, sp, j, *_: (b_, sp, g, 0, 0)),
            pl.BlockSpec((1, 1, 1, rep, 1),
                         lambda b_, g, sp, j, *_: (b_, sp, g, 0, 0)),
            pl.BlockSpec((1, 1, 1, rep, 1),
                         lambda b_, g, sp, j, *_: (b_, sp, g, 0, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((rep, hd), jnp.float32),
            pltpu.VMEM((rep, 1), jnp.float32),
            pltpu.VMEM((rep, 1), jnp.float32),
        ],
    )
    acc, m, l = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((b, splits, kv, rep, hd), jnp.float32),
            jax.ShapeDtypeStruct((b, splits, kv, rep, 1), jnp.float32),
            jax.ShapeDtypeStruct((b, splits, kv, rep, 1), jnp.float32),
        ],
        compiler_params=grid_compiler_params(dims, 3, 1),
        interpret=interpret,
    )(length, q, k, v)
    # combine the per-split partials with one logsumexp rescale
    m_tot = m.max(axis=1, keepdims=True)              # (b, 1, kv, rep, 1)
    w = jnp.exp(m - m_tot)
    l_tot = (l * w).sum(axis=1)
    o = (acc * w).sum(axis=1)
    return o / jnp.maximum(l_tot, 1e-30)
