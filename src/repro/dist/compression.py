"""Gradient compression substrates with error feedback.

Two wire formats and the error-feedback (EF) wrapper that makes them safe
for SGD/Adam:

  * ``quantize_int8``/``dequantize_int8`` — per-tensor absmax int8; the
    roundtrip error is bounded by ``absmax/254`` per element.
  * ``topk_compress``/``topk_decompress`` — keep the ``frac`` fraction of
    largest-|g| entries as (values, flat indices).

``compress_with_feedback`` implements the standard EF recurrence
(Seide et al. / Karimireddy et al.): the residual of each step's
compression is added back into the next step's gradient, so the scheme
stays unbiased in the long run and convergence matches uncompressed
training closely (tested in ``tests/test_substrates.py``).

``compressed_allreduce_mean`` is the collective: each shard quantizes its
local block before the reduction, modelling an int8-on-the-wire
all-reduce; ``wire_bytes`` accounts for exactly what such a transport
would move per step (the number the roofline's collective term wants).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

__all__ = [
    "CompressionConfig", "compress_with_feedback", "init_error_state",
    "quantize_int8", "dequantize_int8", "topk_compress", "topk_decompress",
    "compressed_allreduce_mean", "wire_bytes",
]


@dataclass(frozen=True)
class CompressionConfig:
    """Wire-format knobs: ``scheme`` in {"none", "int8", "topk"};
    ``topk_frac`` is the kept fraction for the top-k scheme."""

    scheme: str = "none"
    topk_frac: float = 0.25

    def __post_init__(self):
        if self.scheme not in ("none", "int8", "topk"):
            raise ValueError(f"unknown compression scheme {self.scheme!r}")


# -- int8 ----------------------------------------------------------------------

def quantize_int8(x: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Per-tensor absmax quantization -> (int8 codes, f32 scale)."""
    x32 = x.astype(jnp.float32)
    scale = jnp.max(jnp.abs(x32)) / 127.0
    q = jnp.round(x32 / jnp.maximum(scale, 1e-30))
    return jnp.clip(q, -127, 127).astype(jnp.int8), scale.astype(jnp.float32)


def dequantize_int8(q: jax.Array, scale: jax.Array,
                    shape: tuple[int, ...]) -> jax.Array:
    return (q.astype(jnp.float32) * scale).reshape(shape)


# -- top-k ----------------------------------------------------------------------

def _topk_k(n: int, frac: float) -> int:
    return max(1, min(n, int(round(n * frac))))


def topk_compress(x: jax.Array, frac: float) -> tuple[jax.Array, jax.Array]:
    """Keep the ``frac`` largest-|x| entries -> (values, flat int32 idx)."""
    flat = x.reshape(-1).astype(jnp.float32)
    k = _topk_k(flat.shape[0], frac)
    _, idx = jax.lax.top_k(jnp.abs(flat), k)
    return flat[idx], idx.astype(jnp.int32)


def topk_decompress(values: jax.Array, idx: jax.Array,
                    shape: tuple[int, ...]) -> jax.Array:
    n = 1
    for d in shape:
        n *= d
    out = jnp.zeros((n,), jnp.float32).at[idx].set(values)
    return out.reshape(shape)


# -- error feedback -------------------------------------------------------------

def init_error_state(params: Any) -> Any:
    """Zero EF residual tree, shaped (and shardable) like the params."""
    return jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)


def _compress_leaf(g: jax.Array, cfg: CompressionConfig) -> jax.Array:
    """Compress-then-decompress one leaf (the EF update needs the
    decompressed representative anyway)."""
    if cfg.scheme == "int8":
        q, s = quantize_int8(g)
        return dequantize_int8(q, s, g.shape)
    v, i = topk_compress(g, cfg.topk_frac)
    return topk_decompress(v, i, g.shape)


def compress_with_feedback(grads: Any, err: Any, cfg: CompressionConfig
                           ) -> tuple[Any, Any]:
    """EF step: compress (grad + residual), carry the new residual.

    Returns ``(compressed_grads, new_err)`` with the same tree structure
    as ``grads``; with ``scheme="none"`` it is the identity.
    """
    if cfg.scheme == "none":
        return grads, err

    def leaf(g, e):
        total = g.astype(jnp.float32) + e
        c = _compress_leaf(total, cfg)
        return c.astype(g.dtype), total - c

    flat_g, tdef = jax.tree.flatten(grads)
    flat_e = tdef.flatten_up_to(err)
    out = [leaf(g, e) for g, e in zip(flat_g, flat_e)]
    return (tdef.unflatten([o[0] for o in out]),
            tdef.unflatten([o[1] for o in out]))


# -- collectives ----------------------------------------------------------------

def compressed_allreduce_mean(x: jax.Array, mesh, axis: str,
                              scheme: str = "int8",
                              topk_frac: float = 0.25) -> jax.Array:
    """All-reduce-mean of ``x`` over mesh axis ``axis`` with each shard's
    contribution compressed before the reduction.

    ``x``'s leading dimension is sharded over ``axis``; the result has
    ``x``'s shape with every row holding the global mean (what an
    int8-on-the-wire ring all-reduce delivers, error model included).
    """
    cfg = CompressionConfig(scheme=scheme, topk_frac=topk_frac)
    size = mesh.shape[axis]

    def local(xl):
        contrib = xl.astype(jnp.float32)
        if cfg.scheme != "none":
            contrib = _compress_leaf(contrib, cfg)
        return jax.lax.psum(contrib, axis) / size

    spec = P(axis, *([None] * (x.ndim - 1)))
    return jax.shard_map(local, mesh=mesh, in_specs=(spec,), out_specs=spec,
                         check_vma=False)(x)


# -- wire accounting ------------------------------------------------------------

def wire_bytes(grads: Any, cfg: CompressionConfig) -> int:
    """Bytes one replica puts on the wire per step under ``cfg``.

    none: raw elements at their dtype width.  int8: one byte per element
    plus a f32 scale per leaf.  topk: (f32 value + int32 index) per kept
    entry.
    """
    total = 0
    for g in jax.tree.leaves(grads):
        n = g.size
        if cfg.scheme == "none":
            total += n * jnp.dtype(g.dtype).itemsize
        elif cfg.scheme == "int8":
            total += n + 4
        else:
            total += _topk_k(n, cfg.topk_frac) * (4 + 4)
    return total
