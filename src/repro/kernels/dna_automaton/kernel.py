"""Chunk-parallel finite-automaton matching as Pallas TPU kernels.

The paper's application is DFA-based DNA motif search (PaREM).  A DFA is
sequential per symbol, but transition functions COMPOSE: processing a
chunk from every possible start state yields a state-map vector
m: S -> S, and m_ab = m_b[m_a].  That composition is associative — the
classic parallel-FA-matching decomposition, and the reason this workload
is "divisible" in the paper's sense (any chunk boundary works).

Kernel 1 (``state_map``):   grid (n_chunks,) — each cell walks its chunk
    once carrying all S states as scalars (S and n_sym are tiny for DNA
    motifs).
Kernel 2 (``count_hits``):  given each chunk's true start state (from the
    host-side associative compose of the maps), each cell re-walks its
    chunk counting accepting-state visits.

Both walks are scalar table lookups T[state, sym], so the text chunk,
the flattened table and the outputs live in SMEM: Mosaic lowers no 1-D
vector gather.  HBM traffic: the text is read exactly twice; table and
maps are negligible.  Output blocks are (1, 1, n) tiles of
(n_chunks, 1, n) arrays, which the TPU's (8, 128) block rule admits.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import grid_compiler_params, largest_aligned_divisor


def _smem(block_shape, index_map):
    return pl.BlockSpec(block_shape, index_map,
                        memory_space=pltpu.MemorySpace.SMEM)


def _state_map_kernel(text_ref, table_ref, map_ref, *, chunk, s, n_sym):
    def step(t, states):
        sym = text_ref[t]
        return tuple(table_ref[st * n_sym + sym] for st in states)

    states = jax.lax.fori_loop(0, chunk, step,
                               tuple(jnp.int32(i) for i in range(s)))
    for i, st in enumerate(states):
        map_ref[0, 0, i] = st


def state_map_kernel(text, table, *, chunk: int = 2048,
                     dims: str = "parallel", interpret: bool = False):
    """text: (T,) int32; table: (S, n_sym) int32 -> maps (T/chunk, S)."""
    t = text.shape[0]
    chunk = largest_aligned_divisor(t, chunk, align=128)
    n_chunks = t // chunk
    s, n_sym = table.shape
    return pl.pallas_call(
        functools.partial(_state_map_kernel, chunk=chunk, s=s, n_sym=n_sym),
        grid=(n_chunks,),
        in_specs=[
            _smem((chunk,), lambda i: (i,)),
            _smem((s * n_sym,), lambda i: (0,)),
        ],
        out_specs=_smem((1, 1, s), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((n_chunks, 1, s), jnp.int32),
        compiler_params=grid_compiler_params(dims, 1, 0),
        interpret=interpret,
    )(text.astype(jnp.int32), table.astype(jnp.int32).reshape(-1))[:, 0]


def _count_kernel(text_ref, table_ref, accept_ref, start_ref,
                  count_ref, state_ref, *, chunk, n_sym):
    def step(t, carry):
        state, hits = carry
        state = table_ref[state * n_sym + text_ref[t]]
        return state, hits + accept_ref[state]

    state, hits = jax.lax.fori_loop(
        0, chunk, step, (start_ref[pl.program_id(0)], jnp.int32(0)))
    count_ref[0, 0, 0] = hits
    state_ref[0, 0, 0] = state


def count_hits_kernel(text, table, accept, starts, *, chunk: int = 2048,
                      dims: str = "parallel", interpret: bool = False):
    """Counts accepting visits per chunk given per-chunk start states."""
    t = text.shape[0]
    chunk = largest_aligned_divisor(t, chunk, align=128)
    n_chunks = t // chunk
    s, n_sym = table.shape
    counts, states = pl.pallas_call(
        functools.partial(_count_kernel, chunk=chunk, n_sym=n_sym),
        grid=(n_chunks,),
        in_specs=[
            _smem((chunk,), lambda i: (i,)),
            _smem((s * n_sym,), lambda i: (0,)),
            _smem((s,), lambda i: (0,)),
            _smem((n_chunks,), lambda i: (0,)),
        ],
        out_specs=[
            _smem((1, 1, 1), lambda i: (i, 0, 0)),
            _smem((1, 1, 1), lambda i: (i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n_chunks, 1, 1), jnp.int32),
            jax.ShapeDtypeStruct((n_chunks, 1, 1), jnp.int32),
        ],
        compiler_params=grid_compiler_params(dims, 1, 0),
        interpret=interpret,
    )(text.astype(jnp.int32), table.astype(jnp.int32).reshape(-1),
      accept.astype(jnp.int32), starts.astype(jnp.int32))
    return counts[:, 0, 0], states[:, 0, 0]
