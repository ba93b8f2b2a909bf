"""Pallas TPU kernels for the repo's compute hot spots.

Each kernel lives in its own package: ``kernel.py`` (the Pallas grid
program), ``ops.py`` (the jit'd public wrapper, differentiable where
training needs it) and ``ref.py`` (the pure-jnp oracle the kernel is
validated against).

Launch parameters (block sizes, chunk lengths, grid-dimension
semantics) are tunable: every ``ops.py`` entry point accepts explicit
overrides, and a ``tuned=`` switch that resolves the cached best
configuration for the call's shape/dtype from ``repro.tune.kernels``
(the paper's combinatorial-search loop applied to the kernels
themselves).  This module holds the two pieces shared by all kernels:

  * :func:`largest_aligned_divisor` — clamp a requested block size to a
    valid divisor of the extent (preferring hardware-aligned multiples),
  * :func:`resolve_launch_params` — defaults < tuned cache < explicit
    overrides, with the tuned lookup deferred so the kernels stay
    importable without the tuning stack.
"""

from __future__ import annotations

import sys
from typing import Any, Mapping

__all__ = ["largest_aligned_divisor", "grid_compiler_params",
           "resolve_launch_params"]


def largest_aligned_divisor(n: int, cap: int, align: int = 1) -> int:
    """Largest divisor of ``n`` that is ``<= cap``, preferring multiples
    of ``align`` (sublane/lane tiling) when any exist under the cap.

    Replaces the per-kernel ``while n % block: block -= 1`` linear scans:
    divisors are enumerated in O(sqrt n), and the alignment preference
    keeps clamped blocks on the TPU tile grid (8 sublanes for f32)
    instead of landing on an arbitrary odd divisor.  ``n >= 1`` always
    yields at least 1.
    """
    if n < 1:
        raise ValueError(f"extent must be >= 1, got {n}")
    cap = max(min(cap, n), 1)
    divisors = []
    i = 1
    while i * i <= n:
        if n % i == 0:
            if i <= cap:
                divisors.append(i)
            if n // i <= cap:
                divisors.append(n // i)
        i += 1
    aligned = [d for d in divisors if d % align == 0]
    return max(aligned or divisors)


def grid_compiler_params(dims: str, n_parallel: int, n_carry: int):
    """Mosaic compiler params for a kernel grid: the first ``n_parallel``
    grid dimensions get ``dims`` semantics (``"parallel"`` lets Mosaic
    reorder/parallelize them, ``"arbitrary"`` keeps the nested-loop
    order), and the trailing ``n_carry`` dimensions — those carrying
    VMEM scratch state — are always ``"arbitrary"``.  This is the
    grid-layout variant in each kernel's tuning space; interpret mode
    accepts and ignores it.
    """
    from jax.experimental.pallas import tpu as pltpu  # deferred, like jax

    if dims not in ("parallel", "arbitrary"):
        raise ValueError(f"dims must be 'parallel' or 'arbitrary', "
                         f"got {dims!r}")
    semantics = (dims,) * n_parallel + ("arbitrary",) * n_carry
    return pltpu.CompilerParams(dimension_semantics=semantics)


def resolve_launch_params(kernel: str, meta: Mapping[str, Any], dtype: Any,
                          *, defaults: Mapping[str, Any],
                          overrides: Mapping[str, Any] | None = None,
                          tuned: bool | None = None) -> dict:
    """Launch parameters for one kernel call.

    Precedence: hardcoded ``defaults`` < tuned-store best config <
    caller ``overrides`` (entries that are not ``None``).  ``tuned=None``
    consults the cache only when kernel tuning was enabled globally
    (``repro.tune.kernels.configure``); ``tuned=True`` always consults
    it; ``tuned=False`` never does.  The lookup happens at trace time
    (shapes are static) and performs zero measurements — a store miss
    falls back to the defaults.
    """
    params = dict(defaults)
    # tuned=None can only resolve after repro.tune.kernels.configure()
    # ran, which requires the module to be imported — so when it is not
    # in sys.modules, skip without pulling in the tuning stack at all
    if tuned or (tuned is None and "repro.tune.kernels" in sys.modules):
        from ..tune import kernels as ktune
        if tuned or ktune.tuning_enabled():
            best = ktune.resolve_config(kernel, meta, dtype)
            params.update({k: v for k, v in best.items() if k in params})
    if overrides:
        params.update({k: v for k, v in overrides.items() if v is not None})
    return params
