"""Sequence-sharded single-token decode attention.

For long-context decode the KV cache is sharded along its *sequence*
dimension (each shard owns a contiguous stripe of positions).  One decode
step is then:

  1. the shard whose stripe contains ``pos`` writes the new K/V row
     locally (everyone runs the same masked dynamic-update, so no
     divergence between shards);
  2. every shard runs flash-decode over its stripe, producing a partial
     (accumulator, logsumexp max, normalizer) triple;
  3. the partials combine across the sequence axes with the standard
     cross-shard logsumexp recombination: ``pmax`` of the maxima, then a
     ``psum`` of the rescaled accumulators/normalizers.

GSPMD lowers the combine to one small all-reduce of (B, H)-shaped
tensors — independent of context length — which is what makes 500k-token
caches servable.  ``models.attention.decode_attention`` dispatches here
whenever the active mesh rules map ``"kv_seq"`` to real axes.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

__all__ = ["seq_decode_attention"]

NEG_INF = -1e30


def seq_decode_attention(q: jax.Array, k_new: jax.Array, v_new: jax.Array,
                         cache_k: jax.Array, cache_v: jax.Array,
                         pos: jax.Array, *, mesh, seq_axes,
                         batch_axes=()) -> tuple[jax.Array, jax.Array,
                                                 jax.Array]:
    """One GQA decode step against a sequence-sharded cache.

    q: (B, H, hd); k_new/v_new: (B, KV, hd); cache k/v: (B, S, KV, hd)
    sharded ``P(batch_axes, seq_axes, None, None)``; ``pos`` scalar int32
    (write position; attention spans positions <= pos).  Returns
    ``(out f32 (B, H, hd), new_cache_k, new_cache_v)`` with the caches
    still sequence-sharded.
    """
    b, h, hd = q.shape
    kv = cache_k.shape[2]
    rep = h // kv
    ba = tuple(batch_axes)
    sa = tuple(seq_axes)

    def local(q, kn, vn, ck, cv, pos):
        s_local = ck.shape[1]
        # flattened shard index along the sequence axes (row-major in the
        # order given, matching PartitionSpec semantics)
        idx = jnp.int32(0)
        for a in sa:
            idx = idx * mesh.shape[a] + jax.lax.axis_index(a)
        s0 = idx * s_local

        # masked local write of the new K/V row at global position `pos`
        li = pos - s0
        in_range = (li >= 0) & (li < s_local)
        lc = jnp.clip(li, 0, s_local - 1)
        ck = jnp.where(in_range,
                       jax.lax.dynamic_update_slice_in_dim(
                           ck, kn[:, None].astype(ck.dtype), lc, 1), ck)
        cv = jnp.where(in_range,
                       jax.lax.dynamic_update_slice_in_dim(
                           cv, vn[:, None].astype(cv.dtype), lc, 1), cv)

        # local flash-decode over this stripe
        bl = q.shape[0]
        qf = (q.astype(jnp.float32) * hd ** -0.5).reshape(bl, kv, rep, hd)
        scores = jnp.einsum("bgrh,bsgh->bgrs", qf, ck.astype(jnp.float32))
        valid = (s0 + jnp.arange(s_local)) <= pos
        scores = jnp.where(valid[None, None, None, :], scores, NEG_INF)
        m = scores.max(axis=-1)                              # (B, KV, rep)
        p = jnp.exp(scores - m[..., None])
        l = p.sum(axis=-1)
        acc = jnp.einsum("bgrs,bsgh->bgrh", p, cv.astype(jnp.float32))

        # cross-shard logsumexp combine (stripes with no valid rows have
        # m = -inf and contribute exactly zero)
        if sa:
            m_all = jax.lax.pmax(m, sa)
            c = jnp.exp(m - m_all)
            l = jax.lax.psum(l * c, sa)
            acc = jax.lax.psum(acc * c[..., None], sa)
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        return out.reshape(bl, h, hd), ck, cv

    row_spec = P(ba if ba else None, None, None)
    cache_spec = P(ba if ba else None, sa if sa else None, None, None)
    # replication checking off: the combine reduces with psum
    fn = jax.shard_map(local, mesh=mesh,
                       in_specs=(row_spec, row_spec, row_spec,
                                 cache_spec, cache_spec, P()),
                       out_specs=(row_spec, cache_spec, cache_spec),
                       check_vma=False)
    return fn(q, k_new, v_new, cache_k, cache_v, pos)
