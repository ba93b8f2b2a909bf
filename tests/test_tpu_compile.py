"""Compile rehearsal for one TPU v5e chip, at the widths the chip runs.

The TPU compiler is installed with JAX and compiles for a chip that is
described rather than attached, so these tests catch on the CPU what
interpret mode cannot: blocks off the (8, 128) tile grid, constructs
Mosaic does not lower, and programs that do not fit the chip's memory.
Nothing runs; a passing compile says nothing about results or speed.

The topology is described inside a module-scoped fixture, never at
import time: only one process may load the TPU library, and every test
worker imports this file.  Kernels are called with ``interpret=False``
because ``jax.default_backend()`` is the CPU here.
"""

from __future__ import annotations

import os
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro import configs
from repro.dist.api import use_rules
from repro.dist.sharding import ShardingConfig
from repro.kernels.decode_attention import ops as da_ops
from repro.kernels.dna_automaton import ops as dna_ops
from repro.kernels.flash_attention import ops as fa_ops
from repro.kernels.mamba_scan import ops as ms_ops
from repro.kernels.rwkv6_wkv import ops as wkv_ops
from repro.launch.serve import compile_decode, serving_model
from repro.models.attention import cache_head_dim
from repro.obs import Observer

HBM_BYTES = 15.75 * 2 ** 30          # what XLA lets one v5e program use


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    # compiles for a described chip cannot be read back from the cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return NamedSharding(Mesh(np.asarray(topo.devices[:1]), ("data",)), P())


def _spec(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _grads(fn, n):
    """d(sum of fn's first output) / d(its first n operands)."""
    def loss(*args):
        out = fn(*args)
        out = out[0] if isinstance(out, tuple) else out
        return out.astype(jnp.float32).sum()
    return lambda *args: jax.grad(loss, argnums=tuple(range(n)))(*args)


# The shapes chip_smoke.py runs: qwen2.5-3b attention (hd 128, 16 heads
# over 2 KV heads, 2048 positions), jamba-v0.1's Mamba (d_inner 8192,
# d_state 16), rwkv6-1.6b (32 heads x 64) and a 1M-symbol DNA text.
def _flash(s):
    q = _spec(s, (1, 2048, 16, 128), jnp.bfloat16)
    return (lambda q, k, v: fa_ops.flash_attention(
        q, k, v, causal=True, interpret=False)), (q, q, q)


def _decode(s):
    return (lambda q, k, v, n: da_ops.decode_attention(
        q, k, v, length=n, interpret=False)), (
        _spec(s, (4, 16, 128), jnp.bfloat16),
        _spec(s, (4, 2048, 2, 128), jnp.bfloat16),
        _spec(s, (4, 2048, 2, 128), jnp.bfloat16), _spec(s, (), jnp.int32))


def _mamba(s):
    x = _spec(s, (1, 512, 8192))
    bc = _spec(s, (1, 512, 16))
    return (lambda *a: ms_ops.selective_scan(*a, interpret=False)), (
        x, x, _spec(s, (8192, 16)), bc, bc, _spec(s, (8192,)))


def _rwkv(s):
    x = _spec(s, (1, 512, 32, 64))
    return (lambda *a: wkv_ops.wkv6(*a, interpret=False)), (
        x, x, x, x, _spec(s, (32, 64)))


def _dna(s):
    return (lambda t, tb, ac: dna_ops.fa_match(t, tb, ac, interpret=False)), (
        _spec(s, (1 << 20,), jnp.uint8), _spec(s, (7, 4), jnp.int32),
        _spec(s, (7,), jnp.bool_))


KERNELS = {"flash": (_flash, 3), "decode": (_decode, 0),
           "mamba": (_mamba, 6), "rwkv6": (_rwkv, 5), "dna": (_dna, 0)}


@pytest.mark.parametrize("name,backward", [
    ("flash", False), ("flash", True), ("decode", False), ("mamba", False),
    ("mamba", True), ("rwkv6", False), ("rwkv6", True), ("dna", False),
])
def test_kernel_compiles_for_v5e(one_chip, name, backward):
    build, n_grad = KERNELS[name]
    fn, args = build(one_chip)
    if backward:
        fn = _grads(fn, n_grad)
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _on_chip(sharding, tree):
    return jax.tree.map(lambda x: _spec(sharding, x.shape, x.dtype), tree)


def _fits(compiled):
    m = compiled.memory_analysis()
    used = (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)
    assert used <= HBM_BYTES, f"{used / 2 ** 30:.2f} GiB"
    return m


def _served(one_chip, arch, rows, prompt, gen, observer=None):
    """The prefill and decode programs the step builder serves a chunk of
    ``rows`` with, compiled for one v5e chip."""
    model = serving_model(configs.get(arch))
    rules = ShardingConfig(data_axes=("data",), model_axes=(), fsdp_axes=(),
                           kv_shard="none", remat=False).rules(one_chip.mesh)
    params = _on_chip(one_chip, jax.eval_shape(model.init,
                                               jax.random.PRNGKey(0)))
    tokens = _spec(one_chip, (rows, prompt), jnp.int32)

    def prefill(p, t):
        return model.prefill(p, t, max_len=prompt + gen)

    with jax.set_mesh(one_chip.mesh), use_rules(rules):
        state = _on_chip(one_chip, jax.eval_shape(prefill, params, tokens)[1])
        decode = compile_decode(
            jax.jit(model.decode_step, donate_argnums=(1,)), params, state,
            _spec(one_chip, (rows, 1), jnp.int32),
            _spec(one_chip, (), jnp.int32), observer)
        return jax.jit(prefill).lower(params, tokens).compile(), decode


def test_qwen_serving_step_fits_one_chip(one_chip):
    """qwen2.5-3b at full width with bf16 weights: the serving replica's
    init, a 4 x 512 prefill into a 544-slot cache and the decode step,
    as the step builder serves them, each compile for v5e and fit its
    memory."""
    model = serving_model(configs.get("qwen2.5-3b"))
    init = jax.jit(model.init, out_shardings=one_chip).lower(
        _spec(one_chip, (2,), jnp.uint32)).compile()
    m = _fits(init)
    assert m.temp_size_in_bytes < 2 ** 28       # no f32 copy of the weights
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    dtypes = {x.dtype for x in jax.tree.leaves(params)}
    assert dtypes == {jnp.dtype(jnp.bfloat16)}
    assert sum(x.size for x in jax.tree.leaves(params)) * 2 > 6e9

    prefill, decode = _served(one_chip, "qwen2.5-3b", 4, 512, 32)
    _fits(prefill)
    _fits(decode)


def test_jamba_serving_pair_fits_one_chip_and_prefill_scans_on_the_kernel(
        one_chip):
    """jamba2-3b's largest chunk of the longdoc cell (4 rows of 8192 + 32
    positions), as the step builder serves it: both programs fit, the
    prefill runs the Pallas selective scan by its name, the decode its
    one-token XLA update, and the decode's compile records the bytes a
    row of each kind of state holds."""
    obs = Observer()
    prefill, decode = _served(one_chip, "jamba2-3b", 4, 8192, 32, obs)
    _fits(prefill)
    _fits(decode)
    calls = re.findall(r"%(mamba_scan(?:\.\d+)?) = .*custom_call_target="
                       r"\"tpu_custom_call\"", prefill.as_text())
    assert calls, "no Pallas selective scan in the prefill"
    assert "tpu_custom_call" not in decode.as_text()
    gauges = obs.metrics.gauges
    # K and V of 2 attention layers: 8224 slots of one 128-wide bf16 head
    assert gauges["decode.state_bytes.kv"].value == 2 * 2 * 8224 * 128 * 2
    # 26 Mamba layers: a 3-token bf16 conv window and an f32 16-state
    # per channel of 5120
    assert gauges["decode.state_bytes.ssm"].value == \
        26 * (3 * 5120 * 2 + 5120 * 16 * 4)


# (arch, rows, prompt, gen): qwen at the shape above; phi3's 8- and 2-row
# chunks of the docqa cell (1024 + 32), whose heads of 96 the cache pads to
# 128 lanes so that its default layout is the one attention reads; jamba's
# 4-row chunk of the longdoc cell (8192 + 32), its 2 attention layers one
# per group of the stack.
SERVED = [("qwen2.5-3b", 4, 512, 32), ("phi3-mini-3.8b", 8, 1024, 32),
          ("phi3-mini-3.8b", 2, 1024, 32), ("jamba2-3b", 4, 8192, 32)]


@pytest.mark.parametrize("arch,rows,prompt,gen", SERVED)
def test_served_decode_updates_cache_in_place(one_chip, arch, rows, prompt,
                                              gen):
    """The decode program as served writes one row per layer into the
    donated cache and copies no layer of it: no copy, and no fusion but
    the in-place row write, has a whole layer's or the whole stack's
    cache shape; its temporaries are small; the cache comes out in the
    format it went in, which is the one prefill emits, so no program
    relays it out between calls."""
    cfg = configs.get(arch)
    obs = Observer()
    prefill, decode = _served(one_chip, arch, rows, prompt, gen, obs)
    m = _fits(decode)
    _fits(prefill)
    assert m.temp_size_in_bytes < 64 * 2 ** 20, m.temp_size_in_bytes
    assert obs.metrics.gauges["decode.temp_bytes"].value == \
        m.temp_size_in_bytes
    fmt = decode.input_formats[0][1]
    assert decode.output_formats[1] == fmt
    assert prefill.output_formats[1] == fmt

    hdp = cache_head_dim(cfg.head_dim)
    dims = f"{prompt + gen},{cfg.n_kv_heads},{rows},{hdp}"
    cache = re.compile(
        rf"^\s*(ROOT )?%(\S+) = bf16\[(1,|{cfg.n_groups},)?{dims}\]"
        rf"\S* (copy|fusion|dynamic-slice)\(")
    fused, writes = False, 0
    for line in decode.as_text().splitlines():
        if re.match(r"^(ENTRY )?%\S+ \(", line):
            fused = "fused" in line.split()[0 if line[0] == "%" else 1]
        hit = cache.match(line)
        if hit and not (hit[4] == "dynamic-slice" and fused):
            assert hit[4] == "fusion" and "dynamic-update-slice" in hit[2], \
                line[:200]
            writes += 1
    assert writes == 2                    # K's and V's row write
