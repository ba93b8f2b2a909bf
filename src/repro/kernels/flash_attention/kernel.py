"""FlashAttention-2 forward + backward Pallas TPU kernels.

Layout: heads are folded into the batch grid dimension; each (bh, q-block)
cell streams k/v blocks through VMEM, carrying the online-softmax state
(acc, running max m, running sum l) in VMEM scratch across the innermost
grid dimension.  MXU-aligned block sizes (multiples of 128 on the lane
dim; hd padded by the caller if needed).

Forward grid:  (B*H, Tq/bq, Tk/bk)    — k innermost, sequential carry
Backward:
  dq grid      (B*H, Tq/bq, Tk/bk)    — recomputes p per block
  dkv grid     (B*H, Tk/bk, Tq/bq)    — q innermost, accumulates dk/dv

The backward uses the saved forward logsumexp (L = m + log l) and
delta = rowsum(do * o), the standard FA-2 decomposition.

Row statistics (m, l, L, delta) are (rows, 1) columns, in VMEM and in
HBM alike: a trailing unit dim keeps every block on the TPU's (8, 128)
tiling, which a (1, block_q) row of a (B*H, T) array would break.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import grid_compiler_params, largest_aligned_divisor

NEG_INF = -1e30


def _mask(iq, ik, bq, bk, q_offset):
    qpos = q_offset + iq * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    kpos = ik * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    return qpos >= kpos


# -- forward --------------------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref,
                acc_ref, m_ref, l_ref, *, scale, causal, q_offset, n_k):
    iq, ik = pl.program_id(1), pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0].astype(jnp.float32) * scale          # (bq, hd)
    k = k_ref[0].astype(jnp.float32)                  # (bk, hd)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))  # (bq, bk)
    if causal:
        s = jnp.where(_mask(iq, ik, q.shape[0], k.shape[0], q_offset),
                      s, NEG_INF)
    m_prev = m_ref[...]                               # (bq, 1)
    m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)
    l_ref[...] = l_ref[...] * alpha + p.sum(axis=1, keepdims=True)
    v = v_ref[0].astype(jnp.float32)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot(p, v)
    m_ref[...] = m_new

    @pl.when(ik == n_k - 1)
    def _final():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)
        lse_ref[0] = m_ref[...] + jnp.log(l)


def flash_attention_fwd(q, k, v, *, causal: bool = True, q_offset: int = 0,
                        block_q: int = 128, block_k: int = 128,
                        dims: str = "parallel", interpret: bool = False):
    """q/k/v: (BH, T, hd) with kv already head-repeated.

    Returns (o, lse) with lse of shape (BH, T, 1).
    """
    bh, tq, hd = q.shape
    tk = k.shape[1]
    block_q = largest_aligned_divisor(tq, block_q, align=8)
    block_k = largest_aligned_divisor(tk, block_k, align=8)
    n_q, n_k = tq // block_q, tk // block_k
    scale = hd ** -0.5
    kernel = functools.partial(_fwd_kernel, scale=scale, causal=causal,
                               q_offset=q_offset, n_k=n_k)
    return pl.pallas_call(
        kernel,
        grid=(bh, n_q, n_k),
        in_specs=[
            pl.BlockSpec((1, block_q, hd), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, hd), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, hd), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, hd), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(q.shape, q.dtype),
            jax.ShapeDtypeStruct((bh, tq, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, hd), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
        ],
        compiler_params=grid_compiler_params(dims, 2, 1),
        interpret=interpret,
    )(q, k, v)


# -- backward ---------------------------------------------------------------------

def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
               acc_ref, *, scale, causal, q_offset, n_k):
    iq, ik = pl.program_id(1), pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32) * scale
    k = k_ref[0].astype(jnp.float32)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))
    if causal:
        s = jnp.where(_mask(iq, ik, q.shape[0], k.shape[0], q_offset),
                      s, NEG_INF)
    p = jnp.exp(s - lse_ref[0])                            # (bq, bk)
    do = do_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)
    dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())))
    ds = p * (dp - delta_ref[0])
    acc_ref[...] += jax.lax.dot(ds, k) * scale

    @pl.when(ik == n_k - 1)
    def _final():
        dq_ref[0] = acc_ref[...].astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dk_ref, dv_ref, dk_acc, dv_acc, *, scale, causal,
                q_offset, n_q):
    ik, iq = pl.program_id(1), pl.program_id(2)

    @pl.when(iq == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    q = q_ref[0].astype(jnp.float32) * scale
    k = k_ref[0].astype(jnp.float32)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))   # (bq, bk)
    if causal:
        s = jnp.where(_mask(iq, ik, q.shape[0], k.shape[0], q_offset),
                      s, NEG_INF)
    p = jnp.exp(s - lse_ref[0])
    do = do_ref[0].astype(jnp.float32)
    dv_acc[...] += jax.lax.dot_general(p, do, (((0,), (0,)), ((), ())))
    v = v_ref[0].astype(jnp.float32)
    dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())))
    ds = p * (dp - delta_ref[0])
    dk_acc[...] += jax.lax.dot_general(ds, q, (((0,), (0,)), ((), ())))

    @pl.when(iq == n_q - 1)
    def _final():
        dk_ref[0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


def flash_attention_bwd(q, k, v, o, lse, do, *, causal: bool = True,
                        q_offset: int = 0, block_q: int = 128,
                        block_k: int = 128, dims: str = "parallel",
                        interpret: bool = False):
    bh, tq, hd = q.shape
    tk = k.shape[1]
    block_q = largest_aligned_divisor(tq, block_q, align=8)
    block_k = largest_aligned_divisor(tk, block_k, align=8)
    n_q, n_k = tq // block_q, tk // block_k
    scale = hd ** -0.5
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1,
                    keepdims=True)                        # (BH, T, 1)

    dq = pl.pallas_call(
        functools.partial(_dq_kernel, scale=scale, causal=causal,
                          q_offset=q_offset, n_k=n_k),
        grid=(bh, n_q, n_k),
        in_specs=[
            pl.BlockSpec((1, block_q, hd), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, hd), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, hd), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_q, hd), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, i, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, hd), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, hd), jnp.float32)],
        compiler_params=grid_compiler_params(dims, 2, 1),
        interpret=interpret,
    )(q, k, v, do, lse, delta)

    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, scale=scale, causal=causal,
                          q_offset=q_offset, n_q=n_q),
        grid=(bh, n_k, n_q),
        in_specs=[
            pl.BlockSpec((1, block_q, hd), lambda b, j, i: (b, i, 0)),
            pl.BlockSpec((1, block_k, hd), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, block_k, hd), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, block_q, hd), lambda b, j, i: (b, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, j, i: (b, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, j, i: (b, i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_k, hd), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, block_k, hd), lambda b, j, i: (b, j, 0)),
        ],
        out_shape=[jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)],
        scratch_shapes=[pltpu.VMEM((block_k, hd), jnp.float32),
                        pltpu.VMEM((block_k, hd), jnp.float32)],
        compiler_params=grid_compiler_params(dims, 2, 1),
        interpret=interpret,
    )(q, k, v, do, lse, delta)
    return dq, dk, dv
