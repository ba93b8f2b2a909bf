"""Launch-parameter spaces for the Pallas kernel suite (fwd and bwd).

Candidate values are shape-independent power-of-two ladders — the same
space structure the paper tunes over (Table I lists raw combinations;
invalid rows are never measured).  Validity is checked per shape:
blocks must divide their extent, chunked passes must nest, every block
dimension must sit on the TPU's (8, 128) tile grid (a multiple of 8 in
the second-to-last position and of 128 in the last, or the whole
extent — Mosaic refuses anything else, which interpret mode never
checks), and the per-cell VMEM footprint must fit the ~16 MiB budget
(pipelined input/output blocks count twice for double buffering;
scratch is allocated once).  ``dims`` is the grid-layout variant:
whether the non-carry grid dimensions are declared ``"parallel"``
(Mosaic may reorder/parallelize) or ``"arbitrary"`` (strict loop
nest).

The scan kernels (``mamba_scan``, ``rwkv6_wkv``) expose a ``lanes``
parameter selecting between the serial per-token grid program
(``lanes=0`` — the hardcoded default, so the bench baseline stays the
serial-scan default) and the chunked parallel-scan formulation
(``lanes >= 2`` chunks scanned per grid cell; see each ``kernel.py``).
Their backward passes are registered as separate ``*_bwd`` spaces over
the same shape metas, so the ``tuned=`` path resolves forward and
backward launch parameters independently for one workload family.

Every spec's ``run`` drives the kernel directly with explicit launch
parameters (never through the ``tuned=`` resolution path), and ``ref``
is the kernel's ``ref.py`` oracle (for ``*_bwd`` specs: ``jax.vjp`` of
that oracle with the same cotangents).
"""

from __future__ import annotations

from typing import Any, Mapping

import numpy as np

import jax
import jax.numpy as jnp

from ...core.space import ConfigSpace, Param
from .evaluate import VMEM_BUDGET_BYTES
from .registry import KernelSpec, register_kernel

__all__ = ["BLOCKS", "CHUNKS", "DIMS", "LANES", "SPLITS"]

BLOCKS = (8, 16, 32, 64, 128, 256, 512, 1024)
CHUNKS = (8, 16, 32, 64, 128, 256, 512, 1024)
TEXT_CHUNKS = (256, 512, 1024, 2048, 4096, 8192, 16384, 32768, 65536, 131072)
DIMS = ("parallel", "arbitrary")
LANES = (0, 4, 8, 16)          # 0 = serial grid program (the default)
SPLITS = (1, 2, 4, 8)


def _f32(n: int) -> int:
    return 4 * int(n)


def _divides(extent: int, block: int, name: str) -> str | None:
    if block > extent:
        return f"{name}={block} exceeds extent {extent}"
    if extent % block:
        return f"{name}={block} does not divide {extent}"
    return None


def _tiled(extent: int, block: int, multiple: int, name: str) -> str | None:
    """A block dimension on the TPU tile grid: a multiple of
    ``multiple`` (8 for a second-to-last dim, 128 for a last dim or a
    rank-1 block) or the whole extent."""
    if block % multiple and block != extent:
        return (f"{name}={block} is off the TPU tile grid (neither a "
                f"multiple of {multiple} nor the extent {extent})")
    return None


def _vmem(block_bytes: int, scratch_bytes: int = 0) -> str | None:
    """Per-cell VMEM estimate.

    Pipelined input/output blocks are double buffered (2x); scratch
    buffers are allocated once for the whole grid, so counting them
    twice would wrongly reject large-scratch chunked configurations.
    """
    total = 2 * block_bytes + scratch_bytes
    if total > VMEM_BUDGET_BYTES:
        return f"VMEM overflow: ~{total >> 20} MiB per grid cell"
    return None


# -- flash attention ------------------------------------------------------------

def _fa_space(meta: Mapping[str, Any]) -> ConfigSpace:
    return ConfigSpace([
        Param("block_q", BLOCKS),
        Param("block_k", BLOCKS),
        Param("dims", DIMS, ordinal=False),
    ])


def _fa_validate(cfg, meta) -> str | None:
    bq, bk, hd = cfg["block_q"], cfg["block_k"], meta["hd"]
    return (_divides(meta["tq"], bq, "block_q")
            or _divides(meta["tk"], bk, "block_k")
            or _tiled(meta["tq"], bq, 8, "block_q")
            or _tiled(meta["tk"], bk, 8, "block_k")
            or _vmem(_f32(2 * bq * hd + 2 * bk * hd + 3 * bq + bq * hd)))


def _fa_inputs(meta, dtype, rng):
    shp = [(meta["bh"], meta["tq"], meta["hd"]),
           (meta["bh"], meta["tk"], meta["hd"])]
    return tuple(jnp.asarray(rng.standard_normal(s), dtype)
                 for s in (shp[0], shp[1], shp[1]))


def _fa_run(cfg, inputs, interpret):
    from ...kernels.flash_attention.kernel import flash_attention_fwd

    q, k, v = inputs
    o, _ = flash_attention_fwd(q, k, v, causal=True,
                               block_q=cfg["block_q"],
                               block_k=cfg["block_k"], dims=cfg["dims"],
                               interpret=interpret)
    return o


def _fa_ref(inputs):
    from ...kernels.flash_attention.ref import attention_ref

    q, k, v = inputs
    return attention_ref(q[:, :, None], k[:, :, None], v[:, :, None],
                         causal=True)[:, :, 0]


register_kernel(KernelSpec(
    name="flash_attention",
    defaults={"block_q": 128, "block_k": 128, "dims": "parallel"},
    space_fn=_fa_space, validate_fn=_fa_validate,
    make_inputs=_fa_inputs, run=_fa_run, ref=_fa_ref,
    default_shape={"bh": 4, "tq": 512, "tk": 512, "hd": 64, "causal": True},
    smoke_shape={"bh": 2, "tq": 128, "tk": 128, "hd": 32, "causal": True},
    atol=2e-4, rtol=2e-4,
))


# -- decode attention -----------------------------------------------------------

def _da_space(meta: Mapping[str, Any]) -> ConfigSpace:
    return ConfigSpace([
        Param("block_s", (64, 128, 256, 512, 1024, 2048, 4096, 8192)),
        Param("splits", SPLITS),
        Param("dims", DIMS, ordinal=False),
    ])


def _da_validate(cfg, meta) -> str | None:
    bs, sp = cfg["block_s"], cfg["splits"]
    hd, rep = meta["hd"], meta["rep"]
    err = _divides(meta["s"], sp, "splits")
    if err:
        return err
    return (_divides(meta["s"] // sp, bs, "block_s")
            or _tiled(meta["s"], bs, 8, "block_s")
            or _vmem(_f32(2 * bs * hd + 2 * rep * hd + 2 * rep),
                     _f32(rep * hd + 2 * rep)))


def _da_inputs(meta, dtype, rng):
    b, kv, rep, hd, s = (meta[k] for k in ("b", "kv", "rep", "hd", "s"))
    q = jnp.asarray(rng.standard_normal((b, kv, rep, hd)), dtype)
    k = jnp.asarray(rng.standard_normal((b, s, kv, hd)), dtype)
    v = jnp.asarray(rng.standard_normal((b, s, kv, hd)), dtype)
    return q, k, v, jnp.asarray([s], jnp.int32)


def _da_run(cfg, inputs, interpret):
    from ...kernels.decode_attention.kernel import decode_attention_kernel

    q, k, v, length = inputs
    return decode_attention_kernel(q, k, v, length, block_s=cfg["block_s"],
                                   splits=cfg["splits"], dims=cfg["dims"],
                                   interpret=interpret)


def _da_ref(inputs):
    from ...kernels.decode_attention.ref import decode_attention_ref

    q, k, v, length = inputs
    b, kv, rep, hd = q.shape
    out = decode_attention_ref(q.reshape(b, kv * rep, hd), k, v,
                               length=length[0])
    return out.reshape(b, kv, rep, hd)


register_kernel(KernelSpec(
    name="decode_attention",
    defaults={"block_s": 512, "splits": 1, "dims": "parallel"},
    space_fn=_da_space, validate_fn=_da_validate,
    make_inputs=_da_inputs, run=_da_run, ref=_da_ref,
    default_shape={"b": 2, "kv": 2, "rep": 4, "hd": 64, "s": 4096},
    smoke_shape={"b": 1, "kv": 2, "rep": 4, "hd": 32, "s": 512},
    atol=2e-4, rtol=2e-4,
))


# -- mamba selective scan -------------------------------------------------------

def _ms_space(meta: Mapping[str, Any]) -> ConfigSpace:
    return ConfigSpace([
        Param("block_d", BLOCKS),
        Param("chunk", CHUNKS),
        Param("lanes", LANES),
        Param("unroll", (1, 4)),
        Param("dims", DIMS, ordinal=False),
    ])


def _ms_validate(cfg, meta) -> str | None:
    bd, chunk, lanes = cfg["block_d"], cfg["chunk"], cfg["lanes"]
    t, s = meta["t"], meta["s"]
    err = (_divides(meta["di"], bd, "block_d")
           or _divides(t, chunk, "chunk")
           or _tiled(meta["di"], bd, 128, "block_d"))
    if err:
        return err
    if lanes == 0:           # serial grid program, (S, bd) state
        from ...kernels.mamba_scan.kernel import serial_chunk
        chunk = serial_chunk(t, chunk)
        if chunk % cfg["unroll"]:
            return f"unroll={cfg['unroll']} does not divide chunk={chunk}"
        return _vmem(_f32(3 * chunk * bd + 3 * bd * s + 2 * chunk * s + bd),
                     _f32(bd * s))
    span = chunk * lanes
    if t % span:
        return f"span chunk*lanes={span} does not divide t={t}"
    # the chunked cell stores per-token (P, Hl) scans for every lane
    return (_tiled(t, span, 8, "chunk*lanes")
            or _vmem(_f32(3 * span * bd + 4 * bd * s + 2 * span * s + bd),
                     _f32((2 * lanes * chunk + 1) * bd * s)))


def _ms_inputs(meta, dtype, rng):
    bt, t, di, s = (meta[k] for k in ("bt", "t", "di", "s"))
    f32 = jnp.float32
    x = jnp.asarray(rng.standard_normal((bt, t, di)), f32)
    delta = jnp.asarray(np.abs(rng.standard_normal((bt, t, di))) * 0.1, f32)
    a = jnp.asarray(-(np.abs(rng.standard_normal((di, s))) + 0.5), f32)
    b = jnp.asarray(rng.standard_normal((bt, t, s)), f32)
    c = jnp.asarray(rng.standard_normal((bt, t, s)), f32)
    d = jnp.asarray(rng.standard_normal(di), f32)
    h0 = jnp.zeros((bt, di, s), f32)
    return x, delta, a, b, c, d, h0


def _ms_run(cfg, inputs, interpret):
    from ...kernels.mamba_scan.kernel import selective_scan_kernel

    return selective_scan_kernel(*inputs, block_d=cfg["block_d"],
                                 chunk=cfg["chunk"], lanes=cfg["lanes"],
                                 unroll=cfg["unroll"], dims=cfg["dims"],
                                 interpret=interpret)


def _ms_ref(inputs):
    from ...kernels.mamba_scan.ref import selective_scan_ref

    return selective_scan_ref(*inputs)


register_kernel(KernelSpec(
    name="mamba_scan",
    defaults={"block_d": 256, "chunk": 64, "lanes": 0, "unroll": 1,
              "dims": "parallel"},
    space_fn=_ms_space, validate_fn=_ms_validate,
    make_inputs=_ms_inputs, run=_ms_run, ref=_ms_ref,
    default_shape={"bt": 2, "t": 512, "di": 512, "s": 8},
    smoke_shape={"bt": 1, "t": 64, "di": 64, "s": 4},
    atol=2e-4, rtol=2e-3,
))


# -- mamba selective scan: backward ---------------------------------------------

def _msb_space(meta: Mapping[str, Any]) -> ConfigSpace:
    return ConfigSpace([
        Param("block_d", BLOCKS),
        Param("chunk", CHUNKS),
        Param("dims", DIMS, ordinal=False),
    ])


def _msb_validate(cfg, meta) -> str | None:
    bd, chunk, s = cfg["block_d"], cfg["chunk"], meta["s"]
    # the reverse cell recomputes the span's states into a VMEM stack
    # and walks them back token by token
    return (_divides(meta["di"], bd, "block_d")
            or _divides(meta["t"], chunk, "chunk")
            or _tiled(meta["di"], bd, 128, "block_d")
            or _tiled(meta["t"], chunk, 8, "chunk")
            or _vmem(_f32(7 * chunk * bd + 6 * chunk * s + 4 * bd * s
                          + 2 * bd),
                     _f32(3 * chunk * bd * s + bd * s)))


def _msb_inputs(meta, dtype, rng):
    inputs = _ms_inputs(meta, dtype, rng)
    bt, t, di, s = (meta[k] for k in ("bt", "t", "di", "s"))
    dy = jnp.asarray(rng.standard_normal((bt, t, di)), jnp.float32)
    dh = jnp.asarray(rng.standard_normal((bt, di, s)), jnp.float32)
    return inputs + (dy, dh)


def _msb_run(cfg, inputs, interpret):
    from ...kernels.mamba_scan.kernel import selective_scan_bwd

    return selective_scan_bwd(*inputs, block_d=cfg["block_d"],
                              chunk=cfg["chunk"], dims=cfg["dims"],
                              interpret=interpret)


def _msb_ref(inputs):
    from ...kernels.mamba_scan.ref import selective_scan_ref

    *primals, dy, dh = inputs
    _, vjp = jax.vjp(lambda *args: selective_scan_ref(*args), *primals)
    return vjp((dy, dh))


register_kernel(KernelSpec(
    name="mamba_scan_bwd",
    defaults={"block_d": 256, "chunk": 64, "dims": "parallel"},
    space_fn=_msb_space, validate_fn=_msb_validate,
    make_inputs=_msb_inputs, run=_msb_run, ref=_msb_ref,
    default_shape={"bt": 2, "t": 512, "di": 512, "s": 8},
    smoke_shape={"bt": 1, "t": 64, "di": 64, "s": 4},
    atol=2e-4, rtol=2e-3,
))


# -- rwkv6 wkv ------------------------------------------------------------------

def _wkv_space(meta: Mapping[str, Any]) -> ConfigSpace:
    return ConfigSpace([
        Param("chunk", CHUNKS),
        Param("lanes", (0, 2, 4, 8)),
        Param("block_h", (1, 2, 4)),
        Param("dims", DIMS, ordinal=False),
    ])


def _wkv_validate(cfg, meta) -> str | None:
    chunk, lanes, bh = cfg["chunk"], cfg["lanes"], cfg["block_h"]
    t, hd = meta["t"], meta["hd"]
    err = (_divides(t, chunk, "chunk")
           or _divides(meta["h"], bh, "block_h"))
    if err:
        return err
    if lanes == 0:           # serial grid program
        return (_tiled(t, chunk, 8, "chunk")
                or _vmem(_f32(5 * chunk * bh * hd + bh * hd),
                         _f32(3 * bh * hd * hd)))
    span = chunk * lanes
    if t % span:
        return f"span chunk*lanes={span} does not divide t={t}"
    if chunk > 64:
        # the matrix form computes k * exp(-cumsum(log w)); past ~64
        # tokens the inverse decay product can overflow f32 (the
        # tuner's parity gate also rejects any config that diverges)
        return f"chunk={chunk} exceeds matrix-form stability cap 64"
    # intra-chunk scores (chunk x chunk) per lane plus chunk temporaries
    return (_tiled(t, span, 8, "chunk*lanes")
            or _vmem(_f32(5 * span * bh * hd + bh * hd),
                     _f32(lanes * bh * (chunk * chunk + 6 * chunk * hd)
                          + 3 * bh * hd * hd)))


def _wkv_inputs(meta, dtype, rng):
    b, t, h, hd = (meta[k] for k in ("b", "t", "h", "hd"))
    f32 = jnp.float32
    r, k, v = (jnp.asarray(rng.standard_normal((b, t, h, hd)) * 0.5, f32)
               for _ in range(3))
    w = jnp.asarray(1.0 / (1.0 + np.exp(-(rng.standard_normal(
        (b, t, h, hd)) + 2))), f32)
    u = jnp.asarray(rng.standard_normal((h, hd)) * 0.1, f32)
    s0 = jnp.zeros((b, h, hd, hd), f32)
    return r, k, v, w, u, s0


def _wkv_run(cfg, inputs, interpret):
    from ...kernels.rwkv6_wkv.kernel import wkv6_kernel

    return wkv6_kernel(*inputs, chunk=cfg["chunk"], lanes=cfg["lanes"],
                       block_h=cfg["block_h"], dims=cfg["dims"],
                       interpret=interpret)


def _wkv_ref(inputs):
    from ...kernels.rwkv6_wkv.ref import wkv6_ref

    r, k, v, w, u, s0 = inputs
    return wkv6_ref(r, k, v, w, u, s0)


register_kernel(KernelSpec(
    name="rwkv6_wkv",
    defaults={"chunk": 64, "lanes": 0, "block_h": 1, "dims": "parallel"},
    space_fn=_wkv_space, validate_fn=_wkv_validate,
    make_inputs=_wkv_inputs, run=_wkv_run, ref=_wkv_ref,
    default_shape={"b": 2, "t": 512, "h": 2, "hd": 48},
    smoke_shape={"b": 1, "t": 64, "h": 1, "hd": 16},
    atol=2e-4, rtol=2e-3,
))


# -- rwkv6 wkv: backward --------------------------------------------------------

def _wkvb_space(meta: Mapping[str, Any]) -> ConfigSpace:
    return ConfigSpace([
        Param("chunk", CHUNKS),
        Param("block_h", (1, 2, 4, 8)),
        Param("dims", DIMS, ordinal=False),
    ])


def _wkvb_validate(cfg, meta) -> str | None:
    chunk, bh, hd = cfg["chunk"], cfg["block_h"], meta["hd"]
    # reverse-cell residuals: per-token kv outer products + state stack
    return (_divides(meta["t"], chunk, "chunk")
            or _divides(meta["h"], bh, "block_h")
            or _tiled(meta["t"], chunk, 8, "chunk")
            or _vmem(_f32(10 * chunk * bh * hd + 2 * bh * hd
                          + 3 * bh * hd * hd),
                     _f32(2 * chunk * bh * hd * hd)))


def _wkvb_inputs(meta, dtype, rng):
    inputs = _wkv_inputs(meta, dtype, rng)
    b, t, h, hd = (meta[k] for k in ("b", "t", "h", "hd"))
    dy = jnp.asarray(rng.standard_normal((b, t, h, hd)), jnp.float32)
    ds = jnp.asarray(rng.standard_normal((b, h, hd, hd)), jnp.float32)
    return inputs + (dy, ds)


def _wkvb_run(cfg, inputs, interpret):
    from ...kernels.rwkv6_wkv.kernel import wkv6_bwd

    return wkv6_bwd(*inputs, chunk=cfg["chunk"], block_h=cfg["block_h"],
                    dims=cfg["dims"], interpret=interpret)


def _wkvb_ref(inputs):
    from ...kernels.rwkv6_wkv.ref import wkv6_ref

    *primals, dy, ds = inputs
    _, vjp = jax.vjp(lambda *args: wkv6_ref(*args), *primals)
    return vjp((dy, ds))


register_kernel(KernelSpec(
    name="rwkv6_wkv_bwd",
    defaults={"chunk": 64, "block_h": 1, "dims": "parallel"},
    space_fn=_wkvb_space, validate_fn=_wkvb_validate,
    make_inputs=_wkvb_inputs, run=_wkvb_run, ref=_wkvb_ref,
    default_shape={"b": 2, "t": 512, "h": 2, "hd": 48},
    smoke_shape={"b": 1, "t": 64, "h": 1, "hd": 16},
    atol=2e-4, rtol=2e-3,
))


# -- DNA automaton --------------------------------------------------------------

def _dna_space(meta: Mapping[str, Any]) -> ConfigSpace:
    return ConfigSpace([
        Param("map_chunk", TEXT_CHUNKS),
        Param("count_chunk", TEXT_CHUNKS),
        Param("dims", DIMS, ordinal=False),
    ])


def _dna_validate(cfg, meta) -> str | None:
    mc, cc, t = cfg["map_chunk"], cfg["count_chunk"], meta["t"]
    err = (_divides(t, mc, "map_chunk") or _divides(t, cc, "count_chunk")
           or _tiled(t, mc, 128, "map_chunk")
           or _tiled(t, cc, 128, "count_chunk"))
    if err:
        return err
    if cc % mc:
        return (f"count_chunk={cc} is not a multiple of map_chunk={mc} "
                "(count start states live at map-chunk boundaries)")
    return None


def _dna_inputs(meta, dtype, rng):
    from ...kernels.dna_automaton.ops import build_motif_dfa

    table, accept = build_motif_dfa(meta.get("motif", "ACGTAC"))
    text = rng.integers(0, 4, meta["t"]).astype(np.uint8)
    return (jnp.asarray(text), jnp.asarray(table, jnp.int32),
            jnp.asarray(accept))


def _dna_run(cfg, inputs, interpret):
    from ...kernels.dna_automaton.ops import fa_match

    text, table, accept = inputs
    return fa_match(text, table, accept, map_chunk=cfg["map_chunk"],
                    count_chunk=cfg["count_chunk"], dims=cfg["dims"],
                    tuned=False, interpret=interpret)


def _dna_ref(inputs):
    from ...kernels.dna_automaton.ref import fa_match_ref

    text, table, accept = inputs
    return fa_match_ref(text, table, accept)[0]


register_kernel(KernelSpec(
    name="dna_automaton",
    defaults={"map_chunk": 2048, "count_chunk": 2048, "dims": "parallel"},
    space_fn=_dna_space, validate_fn=_dna_validate,
    make_inputs=_dna_inputs, run=_dna_run, ref=_dna_ref,
    default_shape={"t": 131072, "s": 7},
    smoke_shape={"t": 4096, "s": 7},
    dtype="uint8",
    atol=0.0, rtol=0.0,
))
