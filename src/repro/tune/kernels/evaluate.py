"""Timed-execution evaluator with a numerical-parity gate.

The paper evaluates a candidate system configuration by running the
experiment; here an experiment is one jitted kernel launch at a
candidate's launch parameters.  :class:`KernelTimer` is the measurement
oracle a :class:`~repro.tune.session.TuningSession` consumes:

  * **validity first** — configs that cannot launch (non-dividing
    blocks, blocks off the TPU tile grid, VMEM overflow, incompatible
    chunking) score ``inf`` without running anything, so the search
    never crashes on them and they cost zero experiments.  A candidate
    that passes validity and still fails to launch also scores ``inf``
    — unless it is the space's default config: the kernel's own
    defaults failing is a broken kernel, not a bad candidate, and
    raises;
  * **parity second** — the candidate's output must match the kernel's
    ``ref.py`` oracle within the spec's tolerance, else ``inf`` (a fast
    config that computes the wrong thing must never win);
  * **then time** — best-of-``repeats`` wall time of the jitted call
    (first call compiles/warms, subsequent calls are timed with
    ``block_until_ready``).

Measurements are deduplicated per config (the paper's effort
accounting: re-measuring a recorded experiment is free), and
``n_measured`` counts actual kernel executions — the number the bench
compares against the space size for the <=5% headline claim.
"""

from __future__ import annotations

import time
from typing import Any, Mapping

import numpy as np

import jax

from .registry import KernelSpec

__all__ = ["KernelTimer", "VMEM_BUDGET_BYTES"]

# Per-core VMEM on current TPUs is ~16 MiB; leave headroom for Mosaic's
# double buffering of in/out blocks (the estimate below already folds a
# 2x pipelining factor in, so the budget is the raw capacity).
VMEM_BUDGET_BYTES = 16 * 1024 * 1024


def _block(out) -> None:
    for leaf in jax.tree.leaves(out):
        blocker = getattr(leaf, "block_until_ready", None)
        if blocker is not None:
            blocker()


class KernelTimer:
    """Measurement oracle: ``cfg -> seconds`` (``inf`` = invalid/diverged).

    One timer holds one (kernel, shape, dtype) worth of inputs and the
    precomputed reference output; every distinct config is measured at
    most once.
    """

    def __init__(self, spec: KernelSpec, meta: Mapping[str, Any], dtype: Any,
                 *, interpret: bool | None = None, repeats: int = 3,
                 seed: int = 0, observer=None):
        self.spec = spec
        self.meta = dict(meta)
        self.dtype = dtype
        if interpret is None:
            interpret = jax.default_backend() == "cpu"
        self.interpret = bool(interpret)
        self.repeats = max(int(repeats), 1)
        self.inputs = spec.make_inputs(self.meta, dtype,
                                       np.random.default_rng(seed))
        self.atol, self.rtol = spec.atol, spec.rtol
        if jax.numpy.dtype(dtype).itemsize < 4:      # bf16/f16/int8 inputs
            self.atol = max(self.atol, 2e-2)
            self.rtol = max(self.rtol, 2e-2)
        self._expected = None
        self._default_key = self._key(spec.default_config(
            spec.space(self.meta), self.meta))
        self._cache: dict[tuple, float] = {}
        self.n_measured = 0          # actual kernel executions (deduplicated)
        self.rejected: dict[tuple, str] = {}   # cfg key -> invalidity reason
        from ...obs import as_observer
        self._obs = as_observer(observer)
        if self._obs is not None:
            m = self._obs.metrics
            self._m_measured = m.counter(f"kernel.{spec.name}.measured")
            self._m_rejected = m.counter(f"kernel.{spec.name}.rejected")
            self._m_cached = m.counter(f"kernel.{spec.name}.cache_hits")
            self._h_time = m.histogram(f"kernel.{spec.name}.t_best_s")

    def _key(self, cfg: Mapping[str, Any]) -> tuple:
        return tuple(sorted((str(k), cfg[k]) for k in cfg))

    @property
    def expected(self):
        if self._expected is None:
            self._expected = self.spec.ref(self.inputs)
        return self._expected

    def _parity_ok(self, out) -> bool:
        got = jax.tree.leaves(out)
        want = jax.tree.leaves(self.expected)
        if len(got) != len(want):
            return False
        for g, w in zip(got, want):
            if not np.allclose(np.asarray(g, np.float32),
                               np.asarray(w, np.float32),
                               atol=self.atol, rtol=self.rtol):
                return False
        return True

    def __call__(self, cfg: Mapping[str, Any]) -> float:
        key = self._key(cfg)
        if key in self._cache:
            if self._obs is not None:
                self._m_cached.inc()
            return self._cache[key]
        reason = self.spec.validate(cfg, self.meta)
        if reason is not None:
            self.rejected[key] = reason
            self._cache[key] = float("inf")
            if self._obs is not None:
                self._m_rejected.inc()
            return float("inf")
        if self._obs is not None:
            with self._obs.tracer.span(f"measure.{self.spec.name}",
                                       cat="tune", args=dict(cfg)):
                score = self._guarded_measure(cfg, key)
        else:
            score = self._guarded_measure(cfg, key)
        self._cache[key] = score
        if self._obs is not None:
            if np.isfinite(score):
                self._m_measured.inc()
                self._h_time.observe(score)
            else:
                self._m_rejected.inc()
        return score

    def _guarded_measure(self, cfg: Mapping[str, Any], key: tuple) -> float:
        try:
            return self._measure(dict(cfg))
        except Exception as exc:            # launch failure = invalid config
            if key == self._default_key:
                raise RuntimeError(
                    f"{self.spec.name}: the default launch config "
                    f"{dict(cfg)} failed at shape {self.meta}") from exc
            self.rejected[key] = f"launch failed: {type(exc).__name__}"
            return float("inf")

    def _measure(self, cfg: dict) -> float:
        spec, interpret = self.spec, self.interpret
        fn = jax.jit(lambda args: spec.run(cfg, args, interpret))
        out = fn(self.inputs)               # compile + warm
        _block(out)
        if not self._parity_ok(out):
            self.rejected[self._key(cfg)] = "parity vs ref.py failed"
            return float("inf")
        times = []
        for _ in range(self.repeats):
            t0 = time.perf_counter()
            _block(fn(self.inputs))
            times.append(time.perf_counter() - t0)
        self.n_measured += 1
        return float(min(times))
