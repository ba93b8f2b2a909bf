"""Jit'd wrapper for the selective-scan kernel (custom_vjp: Pallas backward).

Forward and backward are *separately tunable* Pallas launches: the
forward resolves ``mamba_scan`` launch parameters
(``block_d``/``chunk``/``lanes``/``unroll``/``dims``), the backward
resolves ``mamba_scan_bwd`` (``block_d``/``chunk``/``dims``) for the
same shape — both as defaults < tuned store (``tuned=``, see
``repro.tune.kernels``) < explicit overrides, at trace time.  The
backward recomputes span-boundary states and runs a reverse Pallas
sweep instead of re-differentiating the reference scan, so
``jax.grad`` through ``models/mamba.py`` stays on tuned kernels end to
end with O(inputs) residual memory.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .. import resolve_launch_params
from .kernel import selective_scan_bwd, selective_scan_kernel

DEFAULTS = {"block_d": 256, "chunk": 64, "lanes": 0, "unroll": 1,
            "dims": "parallel"}
BWD_DEFAULTS = {"block_d": 256, "chunk": 64, "dims": "parallel"}
# The served prefill's launch (models/mamba.py on a TPU), chosen by a sweep
# on a v5e chip at its shape, 8192 tokens of d_inner 5120 and d_state 16
# (2 rows: 5.17 ms against DEFAULTS' 35.1 ms; docs/kernels.md).
SERVED = {"block_d": 2560, "chunk": 128, "lanes": 0, "unroll": 8,
          "dims": "parallel"}


@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8, 9))
def _scan(x, delta, a, b, c, d, h0, fwd_params, bwd_params, interpret):
    return selective_scan_kernel(x, delta, a, b, c, d, h0,
                                 **dict(fwd_params), interpret=interpret)


def _scan_fwd(x, delta, a, b, c, d, h0, fwd_params, bwd_params, interpret):
    out = selective_scan_kernel(x, delta, a, b, c, d, h0,
                                **dict(fwd_params), interpret=interpret)
    return out, (x, delta, a, b, c, d, h0)


def _scan_bwd(fwd_params, bwd_params, interpret, res, cts):
    x, delta, a, b, c, d, h0 = res
    dy, dhT = cts
    return selective_scan_bwd(x, delta, a, b, c, d, h0, dy, dhT,
                              **dict(bwd_params), interpret=interpret)


_scan.defvjp(_scan_fwd, _scan_bwd)


def selective_scan(x, delta, a, b, c, d, h0=None, *,
                   block_d: int | None = None, chunk: int | None = None,
                   lanes: int | None = None, unroll: int | None = None,
                   dims: str | None = None, tuned: bool | None = None,
                   interpret: bool | None = None):
    """Differentiable fused selective scan; see kernel.py for layout.

    ``tuned=True`` resolves the cached best launch parameters — forward
    and backward independently — for this (shape, dtype, backend) at
    trace time; ``tuned=None`` does so only when tuning was enabled
    globally (``repro.tune.kernels.configure``).
    """
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    bt, t, di = x.shape
    s = a.shape[1]
    meta = {"bt": bt, "t": t, "di": di, "s": s}
    p = resolve_launch_params(
        "mamba_scan", meta, jnp.float32, defaults=DEFAULTS,
        overrides={"block_d": block_d, "chunk": chunk, "lanes": lanes,
                   "unroll": unroll, "dims": dims},
        tuned=tuned)
    pb = resolve_launch_params(
        "mamba_scan_bwd", meta, jnp.float32, defaults=BWD_DEFAULTS,
        tuned=tuned)
    if h0 is None:
        h0 = jnp.zeros((bt, di, s), jnp.float32)
    f32 = functools.partial(jnp.asarray, dtype=jnp.float32)
    return _scan(f32(x), f32(delta), f32(a), f32(b), f32(c), f32(d),
                 f32(h0), tuple(sorted(p.items())),
                 tuple(sorted(pb.items())), interpret)
