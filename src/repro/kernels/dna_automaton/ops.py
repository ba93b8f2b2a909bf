"""Public API: parallel DFA motif matching + motif-table construction.

``fa_match`` = state-map kernel -> host-side associative compose (an
O(log n_chunks) ``associative_scan`` of S-vectors) -> count kernel.
Composition is ``m_ab = m_b[m_a]`` — tested associative-property via
hypothesis in tests/test_dna_kernel.py.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from .. import largest_aligned_divisor, resolve_launch_params
from .kernel import count_hits_kernel, state_map_kernel

DNA_SYMBOLS = "ACGT"

DEFAULTS = {"map_chunk": 2048, "count_chunk": 2048, "dims": "parallel"}


def build_motif_dfa(motif: str) -> tuple[np.ndarray, np.ndarray]:
    """KMP-style DFA over {A,C,G,T} recognising ``motif`` occurrences.

    Returns (table (S, 4) int32, accept (S,) bool) with S = len(motif)+1;
    the accept state loops via its failure function so overlapping
    occurrences all count.
    """
    m = len(motif)
    sym_of = {c: i for i, c in enumerate(DNA_SYMBOLS)}
    pat = [sym_of[c] for c in motif]
    table = np.zeros((m + 1, 4), np.int32)
    table[0, :] = 0
    if m:
        table[0, pat[0]] = 1
    x = 0
    for j in range(1, m + 1):
        for c in range(4):
            table[j, c] = table[x, c]
        if j < m:
            table[j, pat[j]] = j + 1
            x = table[x, pat[j]]
    accept = np.zeros(m + 1, bool)
    accept[m] = True
    return table, accept


def compose_maps(maps: jax.Array) -> jax.Array:
    """Prefix-compose chunk state maps: out[i] = m_0..i (inclusive)."""
    def combine(a, b):            # a then b
        return jnp.take_along_axis(b, a, axis=-1)

    return jax.lax.associative_scan(combine, maps, axis=0)


def fa_match(text: jax.Array, table: jax.Array, accept: jax.Array, *,
             chunk: int | None = None, map_chunk: int | None = None,
             count_chunk: int | None = None, dims: str | None = None,
             start_state: int = 0, tuned: bool | None = None,
             interpret: bool | None = None) -> jax.Array:
    """Total motif matches in ``text`` ((T,) uint8 symbols). int32 scalar.

    The two passes chunk independently (``map_chunk``/``count_chunk``);
    ``chunk`` sets both at once (legacy knob).  The count pass needs the
    automaton state at its own chunk boundaries, so ``count_chunk`` must
    be a multiple of ``map_chunk`` — otherwise it is clamped down to the
    map granularity.  ``tuned=True`` resolves the cached best launch
    parameters for this (shape, dtype, backend) at trace time;
    ``tuned=None`` does so only when tuning was enabled globally
    (``repro.tune.kernels.configure``).
    """
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    table = jnp.asarray(table, jnp.int32)
    accept = jnp.asarray(accept)
    t = text.shape[0]
    meta = {"t": t, "s": table.shape[0]}
    p = resolve_launch_params(
        "dna_automaton", meta, text.dtype, defaults=DEFAULTS,
        overrides={"map_chunk": map_chunk if map_chunk is not None else chunk,
                   "count_chunk": (count_chunk if count_chunk is not None
                                   else chunk),
                   "dims": dims},
        tuned=tuned)
    mc = largest_aligned_divisor(t, p["map_chunk"], align=128)
    cc = largest_aligned_divisor(t, p["count_chunk"], align=128)
    if cc % mc:
        cc = mc
    maps = state_map_kernel(text, table, chunk=mc,
                            dims=p["dims"], interpret=interpret)
    prefix = compose_maps(maps)                       # (T/mc, S)
    # start state of count chunk k = automaton state at position k*cc,
    # i.e. the prefix map after map chunk k*(cc/mc) - 1
    rep = cc // mc
    starts = jnp.concatenate([
        jnp.asarray([start_state], jnp.int32),
        prefix[rep - 1::rep, start_state][:t // cc - 1].astype(jnp.int32),
    ])
    counts, _ = count_hits_kernel(text, table, accept, starts,
                                  chunk=cc, dims=p["dims"],
                                  interpret=interpret)
    return counts.sum(dtype=jnp.int32)
