"""RWKV-6 wkv recurrence as chunked Pallas TPU kernels.

Forward — two grid programs behind one entry point, both keeping the
(hd x hd) per-head state resident in VMEM scratch carried across the
innermost (time) grid dimension (the CUDA wkv kernel's shared-memory
strategy translated to the TPU memory hierarchy — HBM traffic is one
read of r/k/v/w and one write of y):

  * **serial** (``lanes=0``): grid (B, H/bh, T/L); each cell loads an
    (L, bh, hd) block of r/k/v/w and steps through its L tokens with a
    ``fori_loop``, ``block_h`` heads vectorised per cell.
  * **chunked matrix form** (``lanes>=2``): each cell owns
    ``lanes * chunk`` tokens.  With ``g = cumsum(log w)`` inside a
    chunk, the intra-chunk contribution is a masked (chunk x chunk)
    score GEMM between ``r * exp(g_excl)`` and ``k * exp(-g)``, the
    cross-chunk contribution is one GEMM against the chunk-entry state,
    and per-chunk summaries (total decay ``exp(g_last)``, local state
    from safe ratios ``exp(g_last - g) <= 1``) thread the carried state
    through a Python-unrolled ``lanes``-step combine.  No token loop at
    all — the sequential depth per cell is ``lanes``, and the work is
    MXU-shaped.  ``exp(-g)`` bounds chunk length: ``validate`` caps
    matrix-form chunks at 64 and the tuner's parity gate rejects any
    configuration that overflows on the tuning inputs (trained RWKV
    decays sit near 1; adversarially small ``w`` should stay on the
    serial path).

Backward (``wkv6_bwd``) is recompute-based: a spans pre-pass re-derives
the state at every span boundary, then a reverse grid sweep recomputes
each span's states into a (chunk, bh, hd, hd) VMEM stack (loop form —
decays are only ever multiplied, so it is unconditionally stable) and
runs the hand-derived adjoint recurrence over them, last token first;
per-cell partials for the shared ``u`` are summed by the caller and the
span-entry cotangent becomes the carried adjoint.  Residual memory is
O(inputs).  (The loops are explicit because Mosaic lowers no
``lax.scan`` with stacked operands, which ``jax.vjp`` of a scan would
need.)

State is read out per cell into ``s_out`` so callers can both resume
(decode) and checkpoint the recurrence (matching the chunked-remat
training layout in models/rwkv6.py).

TPU tiling: the entry points take (B, T, H, hd) and run the grid on a
head-major (B, H, T, hd) copy, so every sequence block is a
(span, hd) tile rather than a (block_h, hd) slice of the head axis,
which the (8, 128) tiling rule refuses for ``block_h`` < H.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import grid_compiler_params, largest_aligned_divisor


def _serial_kernel(r_ref, k_ref, v_ref, w_ref, u_ref, s0_ref, y_ref,
                   s_out_ref, s_ref, *, chunk, n_chunks):
    jc = pl.program_id(2)

    @pl.when(jc == 0)
    def _init():
        s_ref[...] = s0_ref[0]

    u = u_ref[:, 0]                                # (bh, hd)

    def step(t, _):
        r_t = r_ref[0, :, t]                       # (bh, hd)
        k_t = k_ref[0, :, t]
        v_t = v_ref[0, :, t]
        w_t = w_ref[0, :, t]
        s = s_ref[...]                             # (bh, hd, hd) key x value
        kv = k_t[..., :, None] * v_t[..., None, :]
        y = ((s + u[..., :, None] * kv) * r_t[..., :, None]).sum(axis=-2)
        y_ref[0, :, t] = y.astype(y_ref.dtype)
        s_ref[...] = w_t[..., :, None] * s + kv
        return ()

    jax.lax.fori_loop(0, chunk, step, ())

    @pl.when(jc == n_chunks - 1)
    def _final():
        s_out_ref[0] = s_ref[...]


def _chunked_kernel(r_ref, k_ref, v_ref, w_ref, u_ref, s0_ref, y_ref,
                    s_out_ref, s_scr, *, lanes, chunk, block_h, n_spans):
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        s_scr[...] = s0_ref[0]

    hd = u_ref.shape[2]
    u = u_ref[:, 0]                                  # (bh, hd)
    def split(ref):                                  # -> (lanes, L, bh, hd)
        return ref[0].reshape(block_h, lanes, chunk, hd).transpose(1, 2, 0, 3)

    rs, ks, vs, ws = split(r_ref), split(k_ref), split(v_ref), split(w_ref)

    logw = jnp.log(ws)
    tril = jnp.tril(jnp.ones((chunk, chunk), jnp.float32))
    g = jnp.einsum("ti,libd->ltbd", tril, logw)      # inclusive cumsum
    g_excl = g - logw
    aa = rs * jnp.exp(g_excl)                        # (lanes, L, bh, hd)
    bb = ks * jnp.exp(-g)
    scores = jnp.einsum("ltbd,libd->lbti", aa, bb)
    mask = jnp.tril(jnp.ones((chunk, chunk), jnp.float32), -1)
    y_intra = jnp.einsum("lbti,libj->ltbj", scores * mask, vs)
    bonus = (rs * u * ks).sum(-1)[..., None] * vs
    # per-chunk summaries: total decay + local state via safe ratios <= 1
    g_last = g[:, -1:]                               # (lanes, 1, bh, hd)
    cc = ks * jnp.exp(g_last - g)
    s_loc = jnp.einsum("libd,libj->lbdj", cc, vs)    # (lanes, bh, hd, hd)
    d_tot = jnp.exp(g_last[:, 0])                    # (lanes, bh, hd)

    s = s_scr[...]
    starts = []
    for l in range(lanes):
        starts.append(s)
        s = d_tot[l][..., :, None] * s + s_loc[l]
    s_scr[...] = s
    s_start = jnp.stack(starts, 0)                   # (lanes, bh, hd, hd)

    @pl.when(j == n_spans - 1)
    def _final():
        s_out_ref[0] = s

    y_inter = jnp.einsum("ltbd,lbdj->ltbj", aa, s_start)
    y = y_intra + y_inter + bonus
    y_ref[0] = y.transpose(2, 0, 1, 3).reshape(block_h, lanes * chunk, hd)


def _heads_major(x):  # (B, T, H, hd) <-> (B, H, T, hd)
    return x.transpose(0, 2, 1, 3)


def _clamp_chunking(t: int, chunk: int, lanes: int) -> tuple[int, int]:
    chunk = largest_aligned_divisor(t, chunk, align=8)
    if lanes >= 2:
        lanes = largest_aligned_divisor(t // chunk, lanes)
    return chunk, (lanes if lanes >= 2 else 0)


def wkv6_kernel(r, k, v, w, u, s0, *, chunk: int = 64, lanes: int = 0,
                block_h: int = 1, dims: str = "parallel",
                interpret: bool = False):
    """r,k,v,w: (B, T, H, hd) f32; u: (H, hd); s0: (B, H, hd, hd).

    Returns (y (B,T,H,hd) f32, s_T (B,H,hd,hd) f32).  ``lanes=0`` runs
    the serial per-token scan; ``lanes>=2`` the matrix-form chunked
    formulation (``lanes`` chunks of ``chunk`` tokens per grid cell).
    """
    b, t, h, hd = r.shape
    block_h = largest_aligned_divisor(h, block_h)
    chunk, lanes = _clamp_chunking(t, chunk, lanes)
    span = chunk * lanes if lanes else chunk
    n_spans = t // span
    seq_spec = pl.BlockSpec((1, block_h, span, hd),
                            lambda b_, h_, j: (b_, h_, j, 0))
    sspec = pl.BlockSpec((1, block_h, hd, hd),
                         lambda b_, h_, j: (b_, h_, 0, 0))
    if lanes:
        kernel = functools.partial(_chunked_kernel, lanes=lanes, chunk=chunk,
                                   block_h=block_h, n_spans=n_spans)
    else:
        kernel = functools.partial(_serial_kernel, chunk=chunk,
                                   n_chunks=n_spans)
    y, s_t = pl.pallas_call(
        kernel,
        grid=(b, h // block_h, n_spans),
        in_specs=[
            seq_spec, seq_spec, seq_spec, seq_spec,
            pl.BlockSpec((block_h, 1, hd), lambda b_, h_, j: (h_, 0, 0)),
            sspec,
        ],
        out_specs=[seq_spec, sspec],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, t, hd), jnp.float32),
            jax.ShapeDtypeStruct((b, h, hd, hd), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((block_h, hd, hd), jnp.float32)],
        compiler_params=grid_compiler_params(dims, 2, 1),
        interpret=interpret,
    )(_heads_major(r), _heads_major(k), _heads_major(v), _heads_major(w),
      u.reshape(h, 1, hd), s0)
    return _heads_major(y), s_t


# -- backward: spans pre-pass + reverse adjoint sweep ---------------------------

def _spans_kernel(k_ref, v_ref, w_ref, s0_ref, ss_ref, s_scr, *, span):
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        s_scr[...] = s0_ref[0]

    ss_ref[0, 0] = s_scr[...]                     # state entering this span

    def step(t, _):
        k_t = k_ref[0, :, t]
        v_t = v_ref[0, :, t]
        w_t = w_ref[0, :, t]
        kv = k_t[..., :, None] * v_t[..., None, :]
        s_scr[...] = w_t[..., :, None] * s_scr[...] + kv
        return ()

    jax.lax.fori_loop(0, span, step, ())


def _wkv_bwd_kernel(r_ref, k_ref, v_ref, w_ref, u_ref, ss_ref, dy_ref,
                    dsT_ref, dr_ref, dk_ref, dv_ref, dw_ref, du_ref,
                    ds0_ref, g_scr, s_scr, *, chunk, n_spans):
    jr = pl.program_id(2)                         # 0 = last span (reversed)

    @pl.when(jr == 0)
    def _init():
        g_scr[...] = dsT_ref[0]

    u = u_ref[:, 0]                               # (bh, hd)

    # recompute the span from its entry state: s_scr[t] = state before t
    def forward(t, st):
        s_scr[t] = st
        kv = k_ref[0, :, t][..., :, None] * v_ref[0, :, t][..., None, :]
        return w_ref[0, :, t][..., :, None] * st + kv

    jax.lax.fori_loop(0, chunk, forward, ss_ref[0, 0])

    # adjoint of y_t = r_t (S + u k_t v_t^T), S' = w_t S + k_t v_t^T,
    # token by token in reverse; g = dL/dS' (the state after token t)
    def backward(i, carry):
        g, du_acc = carry
        t = chunk - 1 - i
        r_t, k_t, v_t, w_t, dy_t = (ref[0, :, t] for ref in
                                    (r_ref, k_ref, v_ref, w_ref, dy_ref))
        st = s_scr[t]                             # (bh, hd, hd) key x value
        kv = k_t[..., :, None] * v_t[..., None, :]
        ry = r_t[..., :, None] * dy_t[..., None, :]          # dL/dS via y_t
        dkv = g + u[..., :, None] * ry
        dr_ref[0, :, t] = ((st + u[..., :, None] * kv)
                           * dy_t[..., None, :]).sum(axis=-1)
        dk_ref[0, :, t] = (dkv * v_t[..., None, :]).sum(axis=-1)
        dv_ref[0, :, t] = (dkv * k_t[..., :, None]).sum(axis=-2)
        dw_ref[0, :, t] = (g * st).sum(axis=-1)
        return w_t[..., :, None] * g + ry, du_acc + (ry * kv).sum(axis=-1)

    g, du_p = jax.lax.fori_loop(0, chunk, backward,
                                (g_scr[...], jnp.zeros_like(u)))
    du_ref[0, 0, 0] = du_p                        # per-cell partial: summed
    g_scr[...] = g                                # by the caller

    @pl.when(jr == n_spans - 1)
    def _final():
        ds0_ref[0] = g


def wkv6_bwd(r, k, v, w, u, s0, dy, dsT, *, chunk: int = 64,
             block_h: int = 1, dims: str = "parallel",
             interpret: bool = False):
    """Pallas backward pass: grads of (y, s_T) cotangents (dy, dsT) w.r.t.
    every forward operand.  Returns (dr, dk, dv, dw, du, ds0)."""
    b, t, h, hd = r.shape
    block_h = largest_aligned_divisor(h, block_h)
    chunk = largest_aligned_divisor(t, chunk, align=8)
    n_spans = t // chunk
    seq = pl.BlockSpec((1, block_h, chunk, hd),
                       lambda b_, h_, j: (b_, h_, j, 0))
    sspec = pl.BlockSpec((1, block_h, hd, hd),
                         lambda b_, h_, j: (b_, h_, 0, 0))
    uspec = pl.BlockSpec((block_h, 1, hd), lambda b_, h_, j: (h_, 0, 0))

    spans = pl.pallas_call(
        functools.partial(_spans_kernel, span=chunk),
        grid=(b, h // block_h, n_spans),
        in_specs=[seq, seq, seq, sspec],
        out_specs=pl.BlockSpec((1, 1, block_h, hd, hd),
                               lambda b_, h_, j: (b_, j, h_, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, n_spans, h, hd, hd), jnp.float32),
        scratch_shapes=[pltpu.VMEM((block_h, hd, hd), jnp.float32)],
        compiler_params=grid_compiler_params(dims, 2, 1),
        interpret=interpret,
    )(_heads_major(k), _heads_major(v), _heads_major(w), s0)

    seq_r = pl.BlockSpec((1, block_h, chunk, hd),
                         lambda b_, h_, j: (b_, h_, n_spans - 1 - j, 0))
    out = pl.pallas_call(
        functools.partial(_wkv_bwd_kernel, chunk=chunk, n_spans=n_spans),
        grid=(b, h // block_h, n_spans),
        in_specs=[
            seq_r, seq_r, seq_r, seq_r, uspec,
            pl.BlockSpec((1, 1, block_h, hd, hd),
                         lambda b_, h_, j: (b_, n_spans - 1 - j, h_, 0, 0)),
            seq_r, sspec,
        ],
        out_specs=[
            seq_r, seq_r, seq_r, seq_r,
            pl.BlockSpec((1, 1, 1, block_h, hd),
                         lambda b_, h_, j: (b_, n_spans - 1 - j, h_, 0, 0)),
            sspec,
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, t, hd), jnp.float32),
            jax.ShapeDtypeStruct((b, h, t, hd), jnp.float32),
            jax.ShapeDtypeStruct((b, h, t, hd), jnp.float32),
            jax.ShapeDtypeStruct((b, h, t, hd), jnp.float32),
            jax.ShapeDtypeStruct((b, n_spans, h // block_h, block_h, hd),
                                 jnp.float32),
            jax.ShapeDtypeStruct((b, h, hd, hd), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((block_h, hd, hd), jnp.float32),
                        pltpu.VMEM((chunk, block_h, hd, hd), jnp.float32)],
        compiler_params=grid_compiler_params(dims, 2, 1),
        interpret=interpret,
    )(_heads_major(r), _heads_major(k), _heads_major(v), _heads_major(w),
      u.reshape(h, 1, hd), spans, _heads_major(dy), dsT)
    dr, dk, dv, dw, du_p, ds0 = out
    return (_heads_major(dr), _heads_major(dk), _heads_major(dv),
            _heads_major(dw), du_p.sum(axis=(0, 1)).reshape(h, hd), ds0)
