"""Mamba-1 selective-state-space mixer (Jamba's SSM layers).

Training runs the selective scan as a ``lax.scan`` over time with an
fp32 (B, d_inner, d_state) carry — the XLA reference the dry-run lowers.
The Pallas kernel (``repro.kernels.mamba_scan``) fuses the same recurrence
into VMEM for the TPU target and is validated against ``ref.py`` which
mirrors this math.  Prefill picks by platform: on a TPU it runs the
kernel at the launch parameters chosen for the served shape
(``mamba_scan.ops.SERVED``), elsewhere the ``lax.scan``; ``attn_impl``
"pallas" runs the kernel on every path.  Decode keeps a (conv window,
ssm state) pair per layer and advances one token in O(d_inner * d_state).

Jamba normalises dt, B and C after ``x_proj``: RMSNorms of unit initial
scale and epsilon ``norm_eps`` (HF ``JambaMambaMixer``'s ``dt_layernorm``,
``b_layernorm``, ``c_layernorm``).
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from ..dist.api import constrain
from ..kernels.mamba_scan import ops as ms_ops
from .config import ArchConfig, MambaConfig
from .layers import chunked_scan, dense_init

Params = dict[str, Any]


def _dims(cfg: ArchConfig) -> tuple[int, int, int, int]:
    m = cfg.mamba or MambaConfig()
    d_in = m.expand * cfg.d_model
    dt_rank = m.dt_rank or -(-cfg.d_model // 16)
    return d_in, m.d_state, m.d_conv, dt_rank


def init_mamba(key, cfg: ArchConfig) -> Params:
    d = cfg.d_model
    d_in, d_state, d_conv, dt_rank = _dims(cfg)
    dt = cfg.param_dtype
    ks = jax.random.split(key, 6)
    # dt bias initialised so softplus(dt_bias) spans [1e-3, 1e-1] (mamba init)
    u = jax.random.uniform(ks[0], (d_in,))
    dt_init = jnp.exp(u * (jnp.log(0.1) - jnp.log(1e-3)) + jnp.log(1e-3))
    dt_bias = dt_init + jnp.log(-jnp.expm1(-dt_init))  # inverse softplus
    return {
        "dt_norm": jnp.ones((dt_rank,), dt),
        "b_norm": jnp.ones((d_state,), dt),
        "c_norm": jnp.ones((d_state,), dt),
        "in_proj": dense_init(ks[1], (d, 2 * d_in), dt),
        "conv_w": (jax.random.normal(ks[2], (d_conv, d_in)) * d_conv ** -0.5
                   ).astype(dt),
        "conv_b": jnp.zeros((d_in,), dt),
        "x_proj": dense_init(ks[3], (d_in, dt_rank + 2 * d_state), dt),
        "dt_proj": dense_init(ks[4], (dt_rank, d_in), dt),
        "dt_bias": dt_bias.astype(dt),
        "A_log": jnp.log(jnp.arange(1, d_state + 1, dtype=jnp.float32)
                         )[None, :].repeat(d_in, 0).astype(jnp.float32),
        "D": jnp.ones((d_in,), jnp.float32),
        "out_proj": dense_init(ks[5], (d_in, d), dt),
    }


def _causal_conv(x: jax.Array, w: jax.Array, b: jax.Array,
                 prefix: jax.Array | None = None) -> jax.Array:
    """Depthwise causal conv along time. x: (B,T,C), w: (K,C)."""
    k = w.shape[0]
    if prefix is None:
        prefix = jnp.zeros((x.shape[0], k - 1, x.shape[2]), x.dtype)
    xp = jnp.concatenate([prefix, x], axis=1)
    out = sum(xp[:, i:i + x.shape[1]] * w[i] for i in range(k))
    return out + b


def _rms(x: jax.Array, scale: jax.Array, eps: float) -> jax.Array:
    """RMSNorm over the last axis, in float32."""
    inv = jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return x * inv * scale.astype(jnp.float32)


def _ssm_inputs(p: Params, xc: jax.Array, cfg: ArchConfig):
    d_in, d_state, _, dt_rank = _dims(cfg)
    dtc = jnp.dtype(cfg.compute_dtype)
    dbc = xc.astype(dtc) @ p["x_proj"].astype(dtc)
    dt_r, b_ssm, c_ssm = jnp.split(
        dbc.astype(jnp.float32), [dt_rank, dt_rank + d_state], axis=-1)
    dt_r = _rms(dt_r, p["dt_norm"], cfg.norm_eps)
    b_ssm = _rms(b_ssm, p["b_norm"], cfg.norm_eps)
    c_ssm = _rms(c_ssm, p["c_norm"], cfg.norm_eps)
    delta = jax.nn.softplus(
        dt_r @ p["dt_proj"].astype(jnp.float32) + p["dt_bias"].astype(jnp.float32))
    a = -jnp.exp(p["A_log"])                       # (d_in, d_state)
    return delta, a, b_ssm, c_ssm


def apply_mamba(p: Params, x: jax.Array, cfg: ArchConfig,
                return_state: bool = False):
    """Full-sequence path: training, and with ``return_state`` prefill,
    which also returns the decode state. x: (B, T, D)."""
    dtc = jnp.dtype(cfg.compute_dtype)
    xz = x.astype(dtc) @ p["in_proj"].astype(dtc)
    xs, z = jnp.split(xz, 2, axis=-1)
    xs = constrain(xs, "batch", None, "mamba_ff")
    xc = jax.nn.silu(_causal_conv(xs, p["conv_w"].astype(dtc),
                                  p["conv_b"].astype(dtc)))
    delta, a, b_ssm, c_ssm = _ssm_inputs(p, xc, cfg)
    xf = xc.astype(jnp.float32)
    args = (xf, delta, a, b_ssm, c_ssm, p["D"])

    if cfg.attn_impl == "pallas":
        # tuned=None: cached best launch params when kernel tuning is
        # enabled (repro.tune.kernels.configure), defaults otherwise.
        # The op carries a Pallas custom_vjp, so jax.grad through this
        # path runs tuned forward AND backward kernels (the backward
        # resolves its own "mamba_scan_bwd" launch parameters).
        y, h_final = ms_ops.selective_scan(*args, tuned=None)
        seq = None
    elif return_state:
        # prefill: the chip's kernel on a TPU, the lax.scan elsewhere
        y, h_final = jax.lax.platform_dependent(
            *args, tpu=_served_scan, default=_xla_scan)
        seq = "seq"
    else:
        y, h_final = _xla_scan(*args)
        seq = "seq"
    y = y.astype(dtc) * jax.nn.silu(z)
    out = y @ p["out_proj"].astype(dtc)
    out = constrain(out, "batch", seq, None)
    if return_state:
        d_conv = p["conv_w"].shape[0]
        return out, {"conv": xs[:, -(d_conv - 1):], "ssm": h_final}
    return out


def _served_scan(x, delta, a, b, c, d):
    return ms_ops.selective_scan(x, delta, a, b, c, d, interpret=False,
                                 **ms_ops.SERVED)


def _xla_scan(x, delta, a, b, c, d):
    """The selective scan as a ``lax.scan`` over time: (y, final state)."""
    # The (B,T,d_in,d_state) discretised tensors are never materialised:
    # each step builds its own slice, and the chunked scan bounds backward
    # residual memory (see layers.chunked_scan).
    def step(h, inputs):
        delta_t, b_t, c_t, x_t = inputs            # (B,dI),(B,dS),(B,dS),(B,dI)
        da_t = jnp.exp(delta_t[..., None] * a)     # (B, d_in, d_state)
        h = da_t * h + (delta_t * x_t)[..., None] * b_t[:, None, :]
        y = jnp.einsum("bds,bs->bd", h, c_t)
        return h, y

    h0 = jnp.zeros((x.shape[0], x.shape[-1], a.shape[-1]), jnp.float32)
    h_final, ys = chunked_scan(
        step, h0,
        (delta.swapaxes(0, 1), b.swapaxes(0, 1),
         c.swapaxes(0, 1), x.swapaxes(0, 1)),
        chunk=64)
    return ys.swapaxes(0, 1) + x * d, h_final


# -- decode -------------------------------------------------------------------

def init_mamba_state(cfg: ArchConfig, batch: int) -> Params:
    d_in, d_state, d_conv, _ = _dims(cfg)
    return {
        "conv": jnp.zeros((batch, d_conv - 1, d_in), jnp.dtype(cfg.compute_dtype)),
        "ssm": jnp.zeros((batch, d_in, d_state), jnp.float32),
    }


def decode_mamba(p: Params, x: jax.Array, state: Params, cfg: ArchConfig
                 ) -> tuple[jax.Array, Params]:
    """One-token decode. x: (B, 1, D)."""
    dtc = jnp.dtype(cfg.compute_dtype)
    xz = x.astype(dtc) @ p["in_proj"].astype(dtc)
    xs, z = jnp.split(xz, 2, axis=-1)              # (B,1,d_in)
    xc = jax.nn.silu(_causal_conv(xs, p["conv_w"].astype(dtc),
                                  p["conv_b"].astype(dtc),
                                  prefix=state["conv"]))
    new_conv = jnp.concatenate([state["conv"], xs], axis=1)[:, 1:]
    delta, a, b_ssm, c_ssm = _ssm_inputs(p, xc, cfg)
    xf = xc.astype(jnp.float32)
    da = jnp.exp(delta[:, 0, :, None] * a)
    h = da * state["ssm"] + (delta[:, 0, :, None] * b_ssm[:, 0, None, :]
                             * xf[:, 0, :, None])
    y = jnp.einsum("bds,bs->bd", h, c_ssm[:, 0]) + xf[:, 0] * p["D"]
    y = (y[:, None].astype(dtc) * jax.nn.silu(z))
    out = y @ p["out_proj"].astype(dtc)
    return constrain(out, "batch", None, None), {"conv": new_conv, "ssm": h}
