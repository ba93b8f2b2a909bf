"""Per-kernel validation: shape/dtype sweeps + gradients vs pure-jnp oracles.

All kernels run in interpret mode on CPU (the kernel body executes in
Python) — the same code lowers to Mosaic on TPU.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels.flash_attention import ops as fa_ops, ref as fa_ref
from repro.kernels.decode_attention import ops as da_ops, ref as da_ref
from repro.kernels.rwkv6_wkv import ops as wkv_ops, ref as wkv_ref
from repro.kernels.mamba_scan import ops as ms_ops, ref as ms_ref
from repro.kernels.dna_automaton import kernel as dna_kernel
from repro.kernels.dna_automaton import ops as dna_ops, ref as dna_ref

RNG = np.random.default_rng(42)


def _randn(*shape, dtype=jnp.float32, scale=1.0):
    return jnp.asarray(RNG.standard_normal(shape) * scale, dtype)


# -- flash attention ------------------------------------------------------------

@pytest.mark.parametrize("b,t,h,hd,causal,dtype", [
    (2, 256, 4, 64, True, jnp.float32),
    (1, 128, 2, 128, False, jnp.float32),
    (2, 384, 3, 64, True, jnp.float32),
    (1, 256, 2, 64, True, jnp.bfloat16),
])
def test_flash_attention_forward(b, t, h, hd, causal, dtype):
    q, k, v = (_randn(b, t, h, hd, dtype=dtype) for _ in range(3))
    out = fa_ops.flash_attention(q, k, v, causal=causal)
    expect = fa_ref.attention_ref(q, k, v, causal=causal)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expect, np.float32),
                               atol=tol, rtol=tol)


def test_flash_attention_gradients():
    q, k, v = (_randn(2, 256, 2, 64) for _ in range(3))

    def f(impl):
        def loss(q, k, v):
            o = impl(q, k, v)
            return (o.astype(jnp.float32) ** 2).sum()
        return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

    got = f(lambda q, k, v: fa_ops.flash_attention(q, k, v, causal=True))
    want = f(lambda q, k, v: fa_ref.attention_ref(q, k, v, causal=True))
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   atol=5e-4, rtol=5e-4)


def test_flash_attention_q_offset_prefill_continuation():
    q, k, v = (_randn(1, 128, 2, 64) for _ in range(3))
    k2, v2 = _randn(1, 256, 2, 64), _randn(1, 256, 2, 64)
    out = fa_ops.flash_attention(q, k2, v2, causal=True, q_offset=128)
    expect = fa_ref.attention_ref(q, k2, v2, causal=True, q_offset=128)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               atol=2e-5, rtol=2e-5)


# -- decode attention ------------------------------------------------------------

@pytest.mark.parametrize("b,s,kv,rep,hd,length", [
    (2, 1024, 4, 4, 64, 700),
    (1, 512, 2, 8, 128, None),
    (3, 256, 1, 4, 64, 100),
    (2, 512, 8, 1, 64, 512),
])
def test_decode_attention(b, s, kv, rep, hd, length):
    q = _randn(b, kv * rep, hd)
    k = _randn(b, s, kv, hd)
    v = _randn(b, s, kv, hd)
    out = da_ops.decode_attention(q, k, v, length=length, block_s=128)
    expect = da_ref.decode_attention_ref(q, k, v, length=length)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               atol=2e-5, rtol=2e-5)


# -- rwkv6 wkv --------------------------------------------------------------------

@pytest.mark.parametrize("b,t,h,hd,chunk", [
    (2, 128, 2, 32, 32), (1, 96, 1, 64, 16), (2, 64, 4, 16, 64),
])
def test_wkv6_forward_and_state(b, t, h, hd, chunk):
    r, k, v = (_randn(b, t, h, hd, scale=0.5) for _ in range(3))
    w = jnp.asarray(jax.nn.sigmoid(RNG.standard_normal((b, t, h, hd)) + 2),
                    jnp.float32)
    u = _randn(h, hd, scale=0.1)
    y, s = wkv_ops.wkv6(r, k, v, w, u, chunk=chunk)
    ye, se = wkv_ref.wkv6_ref(r, k, v, w, u)
    np.testing.assert_allclose(np.asarray(y), np.asarray(ye), atol=2e-5,
                               rtol=2e-4)
    np.testing.assert_allclose(np.asarray(s), np.asarray(se), atol=2e-5,
                               rtol=2e-4)


def test_wkv6_resume_state_equals_full_run():
    """Processing [0:T/2] then [T/2:T] from the carried state == full run."""
    b, t, h, hd = 1, 64, 2, 16
    r, k, v = (_randn(b, t, h, hd, scale=0.5) for _ in range(3))
    w = jnp.asarray(jax.nn.sigmoid(RNG.standard_normal((b, t, h, hd)) + 2),
                    jnp.float32)
    u = _randn(h, hd, scale=0.1)
    y_full, s_full = wkv_ops.wkv6(r, k, v, w, u, chunk=16)
    half = t // 2
    y1, s1 = wkv_ops.wkv6(r[:, :half], k[:, :half], v[:, :half],
                          w[:, :half], u, chunk=16)
    y2, s2 = wkv_ops.wkv6(r[:, half:], k[:, half:], v[:, half:],
                          w[:, half:], u, s0=s1, chunk=16)
    np.testing.assert_allclose(np.asarray(jnp.concatenate([y1, y2], 1)),
                               np.asarray(y_full), atol=1e-5, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(s2), np.asarray(s_full),
                               atol=1e-5, rtol=1e-4)


def test_wkv6_gradients_match_ref():
    b, t, h, hd = 1, 32, 1, 16
    r, k, v = (_randn(b, t, h, hd, scale=0.5) for _ in range(3))
    w = jnp.asarray(jax.nn.sigmoid(RNG.standard_normal((b, t, h, hd)) + 2),
                    jnp.float32)
    u = _randn(h, hd, scale=0.1)
    g1 = jax.grad(lambda k: wkv_ops.wkv6(r, k, v, w, u, chunk=8)[0].sum())(k)
    g2 = jax.grad(lambda k: wkv_ref.wkv6_ref(r, k, v, w, u)[0].sum())(k)
    np.testing.assert_allclose(np.asarray(g1), np.asarray(g2), atol=1e-5,
                               rtol=1e-4)


def test_wkv6_chunked_matches_serial_at_every_chunk_size():
    """The matrix-form chunked formulation must agree with the serial
    grid program at every chunk size the tuning space can select."""
    from repro.tune import kernels as ktune

    b, t, h, hd = 2, 128, 2, 32
    r, k, v = (_randn(b, t, h, hd, scale=0.5) for _ in range(3))
    w = jnp.asarray(jax.nn.sigmoid(RNG.standard_normal((b, t, h, hd)) + 2),
                    jnp.float32)
    u = _randn(h, hd, scale=0.1)
    y0, s0 = wkv_ops.wkv6(r, k, v, w, u)          # lanes=0: serial default
    spec = ktune.get_kernel("rwkv6_wkv")
    meta = {"b": b, "t": t, "h": h, "hd": hd}
    space = spec.space(meta)
    chunks = space["chunk"].values
    covered = set()
    for chunk in chunks:
        for lanes in space["lanes"].values:
            cfg = {"chunk": chunk, "lanes": lanes, "block_h": 2,
                   "dims": "parallel"}
            if lanes == 0 or spec.validate(cfg, meta) is not None:
                continue
            y, s = wkv_ops.wkv6(r, k, v, w, u, chunk=chunk, lanes=lanes,
                                block_h=2)
            np.testing.assert_allclose(np.asarray(y), np.asarray(y0),
                                       atol=2e-5, rtol=2e-4, err_msg=str(cfg))
            np.testing.assert_allclose(np.asarray(s), np.asarray(s0),
                                       atol=2e-5, rtol=2e-4, err_msg=str(cfg))
            covered.add(chunk)
    # every chunk size the space allows for this shape must be exercised
    assert covered == {c for c in chunks if t % c == 0 and c <= 64}


@pytest.mark.parametrize("b,t,h,hd,chunk,dtype", [
    (1, 32, 1, 16, 8, jnp.float32),
    (2, 64, 2, 32, 16, jnp.float32),
    (1, 64, 2, 16, 32, jnp.bfloat16),
])
def test_wkv6_pallas_backward_matches_ref_grads(b, t, h, hd, chunk, dtype):
    """The recompute-in-backward Pallas sweep vs jax.grad of the ref,
    for every differentiable operand, with a state cotangent in play."""
    r, k, v = (_randn(b, t, h, hd, scale=0.5).astype(dtype)
               for _ in range(3))
    w = jnp.asarray(jax.nn.sigmoid(RNG.standard_normal((b, t, h, hd)) + 2),
                    dtype)
    u = _randn(h, hd, scale=0.1).astype(dtype)

    def loss(fn):
        def inner(r, k, v, w, u):
            y, s = fn(r, k, v, w, u)
            return y.sum() + 0.5 * s.sum()
        return inner

    got = jax.grad(loss(lambda *a: wkv_ops.wkv6(*a, chunk=chunk)),
                   argnums=(0, 1, 2, 3, 4))(r, k, v, w, u)
    # the ops layer computes in f32 regardless of input dtype; hold the
    # ref to the same contract so only input/grad rounding differs
    want = jax.grad(loss(lambda *a: wkv_ref.wkv6_ref(
        *(x.astype(jnp.float32) for x in a))), argnums=(0, 1, 2, 3, 4))(
        r, k, v, w, u)
    tol = 2e-2 if dtype == jnp.bfloat16 else 1e-5
    rtol = 2e-2 if dtype == jnp.bfloat16 else 1e-4
    for name, g1, g2 in zip("rkvwu", got, want):
        np.testing.assert_allclose(np.asarray(g1, np.float32),
                                   np.asarray(g2, np.float32),
                                   atol=tol, rtol=rtol, err_msg=name)


# -- mamba selective scan -----------------------------------------------------------

@pytest.mark.parametrize("bt,t,di,s,block_d,chunk", [
    (2, 64, 128, 8, 64, 16), (1, 128, 64, 16, 64, 32), (3, 32, 96, 4, 32, 8),
])
def test_selective_scan(bt, t, di, s, block_d, chunk):
    x = _randn(bt, t, di)
    delta = jnp.abs(_randn(bt, t, di, scale=0.1))
    a = -(jnp.abs(_randn(di, s)) + 0.5)
    b = _randn(bt, t, s)
    c = _randn(bt, t, s)
    d = _randn(di)
    y, h = ms_ops.selective_scan(x, delta, a, b, c, d, block_d=block_d,
                                 chunk=chunk)
    ye, he = ms_ref.selective_scan_ref(x, delta, a, b, c, d)
    np.testing.assert_allclose(np.asarray(y), np.asarray(ye), atol=2e-5,
                               rtol=2e-4)
    np.testing.assert_allclose(np.asarray(h), np.asarray(he), atol=2e-5,
                               rtol=2e-4)


def test_selective_scan_at_the_served_launch_matches_the_xla_scan():
    """The launch the served prefill runs on a TPU (``ops.SERVED``), in
    interpret mode, against the model's own ``lax.scan`` path: at a
    shape where the launch's block, chunk and lanes tile the extents as
    they do at the served 8192 x 5120 (two blocks, two time spans)."""
    from repro.models.mamba import _xla_scan

    p = ms_ops.SERVED
    span = p["chunk"] * p["lanes"] if p["lanes"] else max(p["chunk"], 128)
    bt, t, di, s = 2, 2 * span, 2 * p["block_d"], 16
    x = _randn(bt, t, di)
    delta = jnp.abs(_randn(bt, t, di, scale=0.1))
    a = -(jnp.abs(_randn(di, s)) + 0.5)
    b, c = _randn(bt, t, s), _randn(bt, t, s)
    d = _randn(di)
    y, h = ms_ops.selective_scan(x, delta, a, b, c, d, interpret=True, **p)
    ye, he = _xla_scan(x, delta, a, b, c, d)
    np.testing.assert_allclose(np.asarray(y), np.asarray(ye), atol=2e-5,
                               rtol=2e-4)
    np.testing.assert_allclose(np.asarray(h), np.asarray(he), atol=2e-5,
                               rtol=2e-4)


@pytest.mark.parametrize("t", [100, 136, 500])
def test_selective_scan_at_the_served_launch_takes_any_length(t):
    """Prompts that are no whole number of the served launch's chunks:
    the serial program pads time, and every token's output and the final
    state still equal the model's own ``lax.scan`` path."""
    from repro.models.mamba import _xla_scan

    p = ms_ops.SERVED
    bt, di, s = 1, p["block_d"], 16
    x = _randn(bt, t, di)
    delta = jnp.abs(_randn(bt, t, di, scale=0.1))
    a = -(jnp.abs(_randn(di, s)) + 0.5)
    b, c = _randn(bt, t, s), _randn(bt, t, s)
    d = _randn(di)
    y, h = ms_ops.selective_scan(x, delta, a, b, c, d, interpret=True, **p)
    ye, he = _xla_scan(x, delta, a, b, c, d)
    assert y.shape == (bt, t, di)
    np.testing.assert_allclose(np.asarray(y), np.asarray(ye), atol=2e-5,
                               rtol=2e-4)
    np.testing.assert_allclose(np.asarray(h), np.asarray(he), atol=2e-5,
                               rtol=2e-4)


def test_selective_scan_refuses_an_unroll_that_does_not_divide_the_chunk():
    bt, t, di, s = 1, 128, 128, 4
    x = _randn(bt, t, di)
    b = _randn(bt, t, s)
    a = -(jnp.abs(_randn(di, s)) + 0.5)
    with pytest.raises(ValueError, match="unroll=3"):
        ms_ops.selective_scan(x, jnp.abs(x), a, b, b, _randn(di),
                              unroll=3, interpret=True)


def test_selective_scan_gradients():
    bt, t, di, s = 1, 16, 32, 4
    x = _randn(bt, t, di)
    delta = jnp.abs(_randn(bt, t, di, scale=0.1))
    a = -(jnp.abs(_randn(di, s)) + 0.5)
    b, c = _randn(bt, t, s), _randn(bt, t, s)
    d = _randn(di)
    g1 = jax.grad(lambda x: ms_ops.selective_scan(
        x, delta, a, b, c, d, block_d=32, chunk=8)[0].sum())(x)
    g2 = jax.grad(lambda x: ms_ref.selective_scan_ref(
        x, delta, a, b, c, d)[0].sum())(x)
    np.testing.assert_allclose(np.asarray(g1), np.asarray(g2), atol=1e-5,
                               rtol=1e-4)


def test_selective_scan_chunked_matches_serial_at_every_chunk_size():
    """The chunked parallel-scan formulation must agree with the serial
    grid program at every chunk size the tuning space can select."""
    from repro.tune import kernels as ktune

    bt, t, di, s = 2, 128, 64, 4
    x = _randn(bt, t, di)
    delta = jnp.abs(_randn(bt, t, di, scale=0.1))
    a = -(jnp.abs(_randn(di, s)) + 0.5)
    b, c = _randn(bt, t, s), _randn(bt, t, s)
    d = _randn(di)
    y0, h0 = ms_ops.selective_scan(x, delta, a, b, c, d)   # lanes=0: serial
    spec = ktune.get_kernel("mamba_scan")
    meta = {"bt": bt, "t": t, "di": di, "s": s}
    space = spec.space(meta)
    chunks = space["chunk"].values
    covered = set()
    for chunk in chunks:
        for lanes in space["lanes"].values:
            cfg = {"block_d": di, "chunk": chunk, "lanes": lanes,
                   "unroll": 1, "dims": "parallel"}
            if lanes == 0 or spec.validate(cfg, meta) is not None:
                continue
            y, h = ms_ops.selective_scan(x, delta, a, b, c, d, block_d=di,
                                         chunk=chunk, lanes=lanes)
            np.testing.assert_allclose(np.asarray(y), np.asarray(y0),
                                       atol=2e-5, rtol=2e-4, err_msg=str(cfg))
            np.testing.assert_allclose(np.asarray(h), np.asarray(h0),
                                       atol=2e-5, rtol=2e-4, err_msg=str(cfg))
            covered.add(chunk)
    # every chunk that can pair with some lane count for t=128 shows up
    assert covered == {c for c in chunks
                       if any(l and t % (c * l) == 0
                              for l in space["lanes"].values)}


@pytest.mark.parametrize("bt,t,di,s,chunk,dtype", [
    (1, 16, 32, 4, 8, jnp.float32),
    (2, 64, 48, 8, 16, jnp.float32),
    (1, 64, 32, 4, 32, jnp.bfloat16),
])
def test_selective_scan_pallas_backward_matches_ref_grads(bt, t, di, s,
                                                          chunk, dtype):
    """The recompute-in-backward Pallas sweep vs jax.grad of the ref,
    for every differentiable operand, with a state cotangent in play."""
    x = _randn(bt, t, di).astype(dtype)
    delta = jnp.abs(_randn(bt, t, di, scale=0.1)).astype(dtype)
    a = -(jnp.abs(_randn(di, s)) + 0.5).astype(dtype)
    b, c = (_randn(bt, t, s).astype(dtype) for _ in range(2))
    d = _randn(di).astype(dtype)

    def loss(fn):
        def inner(x, delta, a, b, c, d):
            y, h = fn(x, delta, a, b, c, d)
            return y.sum() + 0.5 * h.sum()
        return inner

    args = (x, delta, a, b, c, d)
    got = jax.grad(loss(lambda *a_: ms_ops.selective_scan(
        *a_, block_d=32, chunk=chunk)), argnums=tuple(range(6)))(*args)
    # the ops layer computes in f32 regardless of input dtype; hold the
    # ref to the same contract so only input/grad rounding differs
    want = jax.grad(loss(lambda *a_: ms_ref.selective_scan_ref(
        *(v_.astype(jnp.float32) for v_ in a_))),
        argnums=tuple(range(6)))(*args)
    tol = 2e-2 if dtype == jnp.bfloat16 else 1e-5
    rtol = 2e-2 if dtype == jnp.bfloat16 else 1e-4
    for name, g1, g2 in zip(("x", "delta", "a", "b", "c", "d"), got, want):
        np.testing.assert_allclose(np.asarray(g1, np.float32),
                                   np.asarray(g2, np.float32),
                                   atol=tol, rtol=rtol, err_msg=name)


# -- DNA automaton -------------------------------------------------------------------

def _random_text(n, planted, motif="ACGTAC", seed=0):
    rng = np.random.default_rng(seed)
    sym = {c: i for i, c in enumerate("ACGT")}
    text = rng.integers(0, 4, n).astype(np.uint8)
    for pos in planted:
        text[pos:pos + len(motif)] = [sym[c] for c in motif]
    return text


@pytest.mark.parametrize("n,chunk", [(4096, 256), (10000, 512), (4096, 4096)])
def test_fa_match_counts(n, chunk):
    motif = "ACGTAC"
    table, accept = dna_ops.build_motif_dfa(motif)
    text = jnp.asarray(_random_text(n, [3, 100, 101, n - 10]))
    got = int(dna_ops.fa_match(text, table, accept, chunk=chunk))
    want = int(dna_ref.fa_match_ref(text, jnp.asarray(table),
                                    jnp.asarray(accept))[0])
    assert got == want >= 3


def test_overlapping_motif_occurrences():
    table, accept = dna_ops.build_motif_dfa("ACAC")
    sym = {c: i for i, c in enumerate("ACGT")}
    text = jnp.asarray(np.array([sym[c] for c in "ACACACACGG" + "GG" * 27],
                                np.uint8))
    got = int(dna_ops.fa_match(text, table, accept, chunk=16))
    assert got == 3          # ACAC at 0, 2, 4 (overlaps count)


@given(seed=st.integers(0, 1000), split=st.integers(1, 63))
@settings(max_examples=20, deadline=None)
def test_state_map_composition_property(seed, split):
    """process(a+b) == compose(process(a), process(b)) — the associativity
    that makes the workload divisible (the paper's core assumption)."""
    table, _ = dna_ops.build_motif_dfa("ACGT")
    table_j = jnp.asarray(table)
    rng = np.random.default_rng(seed)
    text = jnp.asarray(rng.integers(0, 4, 64).astype(np.uint8))
    m_full = dna_ref.chunk_state_map_ref(text, table_j)
    m_a = dna_ref.chunk_state_map_ref(text[:split], table_j)
    m_b = dna_ref.chunk_state_map_ref(text[split:], table_j)
    np.testing.assert_array_equal(np.asarray(m_full),
                                  np.asarray(m_b)[np.asarray(m_a)])


def test_state_map_kernel_matches_ref():
    table, _ = dna_ops.build_motif_dfa("ACGTAC")
    text = jnp.asarray(_random_text(2048, [7, 99]))
    maps = dna_kernel.state_map_kernel(text, jnp.asarray(table), chunk=256,
                                       interpret=True)
    for i in range(maps.shape[0]):
        want = dna_ref.chunk_state_map_ref(text[i * 256:(i + 1) * 256],
                                           jnp.asarray(table))
        np.testing.assert_array_equal(np.asarray(maps[i]), np.asarray(want))
