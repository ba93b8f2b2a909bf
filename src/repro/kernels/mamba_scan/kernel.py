"""Mamba-1 selective scan as fused Pallas TPU kernels.

Forward — two grid programs behind one entry point:

  * **serial** (``lanes=0``): grid (B, dI/bd, T/L); the state is VMEM
    scratch carried across the innermost time-chunk dimension and each
    cell steps its L tokens sequentially.  This is the CUDA
    selective-scan kernel's strategy mapped onto the TPU memory
    hierarchy: discretised tensors (exp(delta A) etc.) are
    rematerialised per timestep in VREGs and never touch HBM.  The
    state is held as (S, bd), channels in the vector lanes: d_state is
    16, so a (bd, S) state would fill 16 of 128 lanes and need each
    token's x, dt and y moved between lanes and sublanes.  B and C come
    transposed, (S, L) per cell with time in lanes, and each token's
    column is picked out by a lane mask.
  * **chunked** (``lanes>=2``): each cell owns a *span* of
    ``lanes * chunk`` tokens split into ``lanes`` chunks scanned in
    lockstep — the per-token loop runs ``chunk`` iterations with a
    ``(lanes, bd, S)`` carry, storing each token's running decay
    product and zero-state local scan in VMEM.  A Python-unrolled
    ``lanes``-step combine then threads the carried span-entry state
    through the chunk summaries (decay product, local state), and one
    vectorised fixup ``H = H_local + P * h_chunk_start`` + output
    contraction finishes all span tokens at once.  Identical math, but
    the sequential depth per cell drops from ``span`` to
    ``chunk + lanes`` — on backends where the serial loop is
    per-iteration-overhead bound this is the win the tuner finds.

Backward (``selective_scan_bwd``) is recompute-based: a light spans
pre-pass re-derives the state at every span boundary, then a reverse
grid sweep (span index map ``n-1-j``) recomputes each span's states
into a (chunk, bd, S) VMEM stack and runs the hand-derived adjoint
recurrence over them token by token, last token first.  The input
cotangents land in per-cell partial outputs (summed by the caller for
the reduced operands a/b/c/d) and the span-entry cotangent becomes the
carried adjoint for the previous span.  Residual memory is O(inputs):
nothing from the forward pass is saved but the inputs.  (The loops are
explicit because Mosaic lowers no ``lax.scan`` with stacked operands,
which ``jax.vjp`` of a scan would need.)
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import grid_compiler_params, largest_aligned_divisor


def _serial_kernel(x_ref, dt_ref, at_ref, bt_ref, ct_ref, d_ref, h0_ref,
                   y_ref, h_out_ref, h_ref, *, chunk, n_chunks, unroll):
    jc = pl.program_id(2)

    @pl.when(jc == 0)
    def _init():
        h_ref[...] = h0_ref[0]

    at = at_ref[...]                              # (S, bd)
    d = d_ref[...]                                # (1, bd)
    bs, cs = bt_ref[0], ct_ref[0]                 # (S, chunk)
    lane = jax.lax.broadcasted_iota(jnp.int32, bs.shape, 1)

    def step(t, h):
        x_t = x_ref[0, pl.ds(t, 1), :]            # (1, bd)
        dt_t = dt_ref[0, pl.ds(t, 1), :]
        now = lane == t
        b_t = jnp.sum(jnp.where(now, bs, 0.0), axis=1, keepdims=True)
        c_t = jnp.sum(jnp.where(now, cs, 0.0), axis=1, keepdims=True)
        h = jnp.exp(dt_t * at) * h + b_t * (dt_t * x_t)        # (S, bd)
        y_ref[0, pl.ds(t, 1), :] = (jnp.sum(h * c_t, axis=0, keepdims=True)
                                    + d * x_t)
        return h

    def steps(i, h):                  # Mosaic unrolls a loop fully or not
        for k in range(unroll):
            h = step(i * unroll + k, h)
        return h

    h = jax.lax.fori_loop(0, chunk // unroll, steps, h_ref[...])
    h_ref[...] = h

    @pl.when(jc == n_chunks - 1)
    def _final():
        h_out_ref[0] = h


def _chunked_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, d_ref, h0_ref,
                    y_ref, h_out_ref, h_scr, p_scr, hl_scr,
                    *, lanes, chunk, unroll, n_spans):
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        h_scr[...] = h0_ref[0]

    a = a_ref[...]                                     # (bd, S)
    bd, s = a.shape
    xs = x_ref[0].reshape(lanes, chunk, bd)
    dts = dt_ref[0].reshape(lanes, chunk, bd)
    bs = b_ref[0].reshape(lanes, chunk, s)
    cs = c_ref[0].reshape(lanes, chunk, s)

    # all `lanes` chunks scan their tokens in lockstep; P is the running
    # in-chunk decay product, Hl the scan from a zero entry state
    def body(tk, carry):
        p, hl = carry                                  # (lanes, bd, S)
        dt_t = dts[:, tk]                              # (lanes, bd)
        da = jnp.exp(dt_t[..., None] * a[None])        # (lanes, bd, S)
        u = (dt_t * xs[:, tk])[..., None] * bs[:, tk, None, :]
        hl = da * hl + u
        p = p * da
        p_scr[:, tk] = p
        hl_scr[:, tk] = hl
        return p, hl

    zeros = jnp.zeros((lanes, bd, s), jnp.float32)
    p, hl = jax.lax.fori_loop(0, chunk, body, (jnp.ones_like(zeros), zeros),
                              unroll=unroll)

    # thread the carried span-entry state through the chunk summaries
    h = h_scr[...]
    starts = []
    for l in range(lanes):
        starts.append(h)
        h = p[l] * h + hl[l]
    h_scr[...] = h
    hs = jnp.stack(starts, 0)                          # (lanes, bd, S)

    @pl.when(j == n_spans - 1)
    def _final():
        h_out_ref[0] = h

    # fixup every span token at once: h_t = Hl_t + P_t * h_chunk_start
    big = hl_scr[...] + p_scr[...] * hs[:, None]
    y = (big * cs[:, :, None, :]).sum(-1) + d_ref[0] * xs
    y_ref[0] = y.reshape(lanes * chunk, bd)


def serial_chunk(t: int, chunk: int) -> int:
    """The serial program's tokens per cell: ``chunk`` rounded up to a
    multiple of 128 (time is the lane axis of its B and C blocks), and
    no more than ``t`` rounded up likewise.  The scan pads time to a
    whole number of such chunks."""
    return min(-(-max(chunk, 1) // 128), -(-t // 128)) * 128


def _clamp_chunking(t: int, chunk: int, lanes: int) -> tuple[int, int]:
    """Clamp (chunk, lanes) so ``chunk * lanes`` divides ``t``; lanes < 2
    collapses to the serial path (the ``lanes=0`` sentinel)."""
    chunk = largest_aligned_divisor(t, chunk, align=8)
    if lanes >= 2:
        lanes = largest_aligned_divisor(t // chunk, lanes)
    return chunk, (lanes if lanes >= 2 else 0)


def selective_scan_kernel(x, delta, a, b, c, d, h0, *, block_d: int = 256,
                          chunk: int = 64, lanes: int = 0, unroll: int = 1,
                          dims: str = "parallel", interpret: bool = False):
    """x/delta: (B,T,dI) f32; a: (dI,S); b/c: (B,T,S); d: (dI,);
    h0: (B,dI,S).  Returns (y (B,T,dI) f32, h_T (B,dI,S) f32).

    ``lanes=0`` runs the serial per-token scan over chunks of
    ``serial_chunk(T, chunk)`` tokens, ``unroll`` of them a loop
    iteration (``unroll`` must divide the chunk), any T; ``lanes>=2``
    runs the chunked formulation with ``lanes`` chunks of ``chunk`` tokens
    per grid cell (clamped to divide T).
    """
    bt, t, di = x.shape
    s = a.shape[1]
    block_d = largest_aligned_divisor(di, block_d, align=128)
    unroll = max(int(unroll), 1)
    if lanes < 2:
        chunk = serial_chunk(t, chunk)
        if chunk % unroll:
            raise ValueError(f"unroll={unroll} does not divide the serial "
                             f"program's chunk of {chunk} tokens")
        return _serial_scan(x, delta, a, b, c, d, h0, block_d=block_d,
                            chunk=chunk, unroll=unroll, dims=dims,
                            interpret=interpret)
    chunk, lanes = _clamp_chunking(t, chunk, lanes)
    span = chunk * lanes
    n_spans = t // span
    xspec = pl.BlockSpec((1, span, block_d), lambda b_, i, j: (b_, j, i))
    sspec = pl.BlockSpec((1, span, s), lambda b_, i, j: (b_, j, 0))
    hspec = pl.BlockSpec((1, block_d, s), lambda b_, i, j: (b_, i, 0))
    return pl.pallas_call(
        functools.partial(_chunked_kernel, lanes=lanes, chunk=chunk,
                          unroll=unroll, n_spans=n_spans),
        grid=(bt, di // block_d, n_spans),
        in_specs=[
            xspec, xspec,
            pl.BlockSpec((block_d, s), lambda b_, i, j: (i, 0)),
            sspec, sspec,
            pl.BlockSpec((1, block_d), lambda b_, i, j: (0, i)),
            hspec,
        ],
        out_specs=[xspec, hspec],
        out_shape=[
            jax.ShapeDtypeStruct((bt, t, di), jnp.float32),
            jax.ShapeDtypeStruct((bt, di, s), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((block_d, s), jnp.float32),
                        pltpu.VMEM((lanes, chunk, block_d, s), jnp.float32),
                        pltpu.VMEM((lanes, chunk, block_d, s), jnp.float32)],
        compiler_params=grid_compiler_params(dims, 2, 1),
        interpret=interpret,
        name="mamba_scan",
    )(x, delta, a, b, c, d.reshape(1, di), h0)


def _serial_scan(x, delta, a, b, c, d, h0, *, block_d, chunk, unroll, dims,
                 interpret):
    """The serial grid program, on the state's transposed (S, dI) layout.
    Time is padded to whole chunks with delta 0, which leaves the state
    as it is, and the padded outputs are dropped."""
    bt, t, di = x.shape
    s = a.shape[1]
    pad = -t % chunk
    if pad:
        x, delta, b, c = (jnp.pad(v, ((0, 0), (0, pad), (0, 0)))
                          for v in (x, delta, b, c))
    n_chunks = (t + pad) // chunk
    xspec = pl.BlockSpec((1, chunk, block_d), lambda b_, i, j: (b_, j, i))
    sspec = pl.BlockSpec((1, s, chunk), lambda b_, i, j: (b_, 0, j))
    hspec = pl.BlockSpec((1, s, block_d), lambda b_, i, j: (b_, 0, i))
    y, h_t = pl.pallas_call(
        functools.partial(_serial_kernel, chunk=chunk, n_chunks=n_chunks,
                          unroll=unroll),
        grid=(bt, di // block_d, n_chunks),
        in_specs=[
            xspec, xspec,
            pl.BlockSpec((s, block_d), lambda b_, i, j: (0, i)),
            sspec, sspec,
            pl.BlockSpec((1, block_d), lambda b_, i, j: (0, i)),
            hspec,
        ],
        out_specs=[xspec, hspec],
        out_shape=[
            jax.ShapeDtypeStruct((bt, t + pad, di), jnp.float32),
            jax.ShapeDtypeStruct((bt, s, di), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((s, block_d), jnp.float32)],
        compiler_params=grid_compiler_params(dims, 2, 1),
        interpret=interpret,
        name="mamba_scan",
    )(x, delta, a.T, b.swapaxes(1, 2), c.swapaxes(1, 2), d.reshape(1, di),
      h0.swapaxes(1, 2))
    return y[:, :t], h_t.swapaxes(1, 2)


# -- backward: spans pre-pass + reverse adjoint sweep ---------------------------

def _spans_kernel(x_ref, dt_ref, a_ref, b_ref, h0_ref, hs_ref, h_scr,
                  *, span):
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        h_scr[...] = h0_ref[0]

    hs_ref[0, 0] = h_scr[...]                     # state entering this span
    a = a_ref[...]

    def step(t, _):
        dt_t = dt_ref[0, t]
        da = jnp.exp(dt_t[:, None] * a)
        h_scr[...] = (da * h_scr[...]
                      + (dt_t * x_ref[0, t])[:, None] * b_ref[0, t][None, :])
        return ()

    jax.lax.fori_loop(0, span, step, ())


def _scan_bwd_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, d_ref, hs_ref,
                     dy_ref, dhT_ref, dx_ref, ddt_ref, da_ref, db_ref,
                     dc_ref, dd_ref, dh0_ref, g_scr, h_scr, *, chunk,
                     n_spans):
    jr = pl.program_id(2)                         # 0 = last span (reversed)

    @pl.when(jr == 0)
    def _init():
        g_scr[...] = dhT_ref[0]

    a = a_ref[...]                                # (bd, S)
    d = d_ref[0]                                  # (bd,)

    # recompute the span from its entry state: h_scr[t] = state before t
    def forward(t, h):
        h_scr[t] = h
        dt_t = dt_ref[0, t]
        return (jnp.exp(dt_t[:, None] * a) * h
                + (dt_t * x_ref[0, t])[:, None] * b_ref[0, t][None, :])

    jax.lax.fori_loop(0, chunk, forward, hs_ref[0, 0])

    # adjoint of h_t = exp(dt_t a) h_{t-1} + dt_t x_t b_t,
    # y_t = h_t . c_t + d x_t, token by token in reverse; g = dL/dh_t
    def backward(i, carry):
        g, da_acc, dd_acc = carry
        t = chunk - 1 - i
        x_t, dt_t, dy_t = x_ref[0, t], dt_ref[0, t], dy_ref[0, t]
        b_t, c_t = b_ref[0, t], c_ref[0, t]
        h_prev = h_scr[t]
        decay = jnp.exp(dt_t[:, None] * a)
        h_t = decay * h_prev + (dt_t * x_t)[:, None] * b_t[None, :]
        g = g + dy_t[:, None] * c_t[None, :]
        gb = (g * b_t[None, :]).sum(axis=1)
        gh = g * decay * h_prev
        dc_ref[0, 0, t] = (dy_t[:, None] * h_t).sum(axis=0)
        db_ref[0, 0, t] = (g * (dt_t * x_t)[:, None]).sum(axis=0)
        dx_ref[0, t] = dy_t * d + dt_t * gb
        ddt_ref[0, t] = (gh * a).sum(axis=1) + x_t * gb
        return (g * decay, da_acc + gh * dt_t[:, None], dd_acc + dy_t * x_t)

    g, da_p, dd_p = jax.lax.fori_loop(
        0, chunk, backward,
        (g_scr[...], jnp.zeros_like(a), jnp.zeros_like(d)))
    da_ref[0, 0] = da_p                           # per-cell partials: the
    dd_ref[0, 0, 0] = dd_p                        # reduced operands are
    g_scr[...] = g                                # summed by the caller

    @pl.when(jr == n_spans - 1)
    def _final():
        dh0_ref[0] = g


def selective_scan_bwd(x, delta, a, b, c, d, h0, dy, dhT, *,
                       block_d: int = 256, chunk: int = 64,
                       dims: str = "parallel", interpret: bool = False):
    """Pallas backward pass: grads of (y, h_T) cotangents (dy, dhT) w.r.t.
    every forward operand.  Returns (dx, ddelta, da, db, dc, dd, dh0)."""
    bt, t, di = x.shape
    s = a.shape[1]
    block_d = largest_aligned_divisor(di, block_d, align=128)
    chunk = largest_aligned_divisor(t, chunk, align=8)
    n_spans = t // chunk
    n_db = di // block_d
    aspec = pl.BlockSpec((block_d, s), lambda b_, i, j: (i, 0))
    dspec = pl.BlockSpec((1, block_d), lambda b_, i, j: (0, i))

    spans = pl.pallas_call(
        functools.partial(_spans_kernel, span=chunk),
        grid=(bt, n_db, n_spans),
        in_specs=[
            pl.BlockSpec((1, chunk, block_d), lambda b_, i, j: (b_, j, i)),
            pl.BlockSpec((1, chunk, block_d), lambda b_, i, j: (b_, j, i)),
            aspec,
            pl.BlockSpec((1, chunk, s), lambda b_, i, j: (b_, j, 0)),
            pl.BlockSpec((1, block_d, s), lambda b_, i, j: (b_, i, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_d, s),
                               lambda b_, i, j: (b_, j, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bt, n_spans, di, s), jnp.float32),
        scratch_shapes=[pltpu.VMEM((block_d, s), jnp.float32)],
        compiler_params=grid_compiler_params(dims, 2, 1),
        interpret=interpret,
    )(x, delta, a, b, h0)

    rev = lambda b_, i, j: (b_, n_spans - 1 - j, i)          # noqa: E731
    xspec_r = pl.BlockSpec((1, chunk, block_d), rev)
    sspec_r = pl.BlockSpec((1, chunk, s),
                           lambda b_, i, j: (b_, n_spans - 1 - j, 0))
    out = pl.pallas_call(
        functools.partial(_scan_bwd_kernel, chunk=chunk, n_spans=n_spans),
        grid=(bt, n_db, n_spans),
        in_specs=[
            xspec_r, xspec_r, aspec, sspec_r, sspec_r, dspec,
            pl.BlockSpec((1, 1, block_d, s),
                         lambda b_, i, j: (b_, n_spans - 1 - j, i, 0)),
            xspec_r,
            pl.BlockSpec((1, block_d, s), lambda b_, i, j: (b_, i, 0)),
        ],
        out_specs=[
            xspec_r, xspec_r,
            pl.BlockSpec((1, 1, block_d, s),
                         lambda b_, i, j: (b_, n_spans - 1 - j, i, 0)),
            pl.BlockSpec((1, 1, chunk, s),
                         lambda b_, i, j: (i, b_, n_spans - 1 - j, 0)),
            pl.BlockSpec((1, 1, chunk, s),
                         lambda b_, i, j: (i, b_, n_spans - 1 - j, 0)),
            pl.BlockSpec((1, 1, 1, block_d),
                         lambda b_, i, j: (b_, n_spans - 1 - j, 0, i)),
            pl.BlockSpec((1, block_d, s), lambda b_, i, j: (b_, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bt, t, di), jnp.float32),
            jax.ShapeDtypeStruct((bt, t, di), jnp.float32),
            jax.ShapeDtypeStruct((bt, n_spans, di, s), jnp.float32),
            jax.ShapeDtypeStruct((n_db, bt, t, s), jnp.float32),
            jax.ShapeDtypeStruct((n_db, bt, t, s), jnp.float32),
            jax.ShapeDtypeStruct((bt, n_spans, 1, di), jnp.float32),
            jax.ShapeDtypeStruct((bt, di, s), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((block_d, s), jnp.float32),
                        pltpu.VMEM((chunk, block_d, s), jnp.float32)],
        compiler_params=grid_compiler_params(dims, 2, 1),
        interpret=interpret,
    )(x, delta, a, b, c, d.reshape(1, di), spans, dy, dhT)
    dx, ddt, da_p, db_p, dc_p, dd_p, dh0 = out
    return (dx, ddt, da_p.sum(axis=(0, 1)), db_p.sum(axis=0),
            dc_p.sum(axis=0), dd_p.sum(axis=(0, 1, 2)), dh0)
