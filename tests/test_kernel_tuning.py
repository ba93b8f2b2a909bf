"""repro.tune.kernels: registry completeness, tuned-path parity vs
ref.py, cache round-trips (0 measurements on repeat), graceful fallback
when the store has no entry, and the shared divisor helper."""

import numpy as np
import pytest

import jax.numpy as jnp

from repro.kernels import largest_aligned_divisor
from repro.runtime.store import TuningStore
from repro.tune import kernels as ktune
from repro.tune.kernels import KernelTimer


@pytest.fixture
def tuned_path_disabled():
    """Ensure the global tuned-path state never leaks across tests."""
    yield
    ktune.disable()


# -- largest_aligned_divisor -----------------------------------------------------

def test_divisor_basic_and_alignment():
    assert largest_aligned_divisor(512, 128) == 128
    assert largest_aligned_divisor(512, 1000) == 512
    assert largest_aligned_divisor(384, 128, align=8) == 128
    # 96 caps at divisors {1..96}: prefers 48 (multiple of 8) over 96? no:
    # 96 divides 96 and 96 % 8 == 0 -> 96 itself
    assert largest_aligned_divisor(96, 96, align=8) == 96
    # no aligned divisor under the cap -> largest unaligned divisor
    assert largest_aligned_divisor(15, 6, align=8) == 5
    assert largest_aligned_divisor(7, 3) == 1
    with pytest.raises(ValueError):
        largest_aligned_divisor(0, 4)


def test_divisor_matches_linear_scan():
    rng = np.random.default_rng(0)
    for _ in range(200):
        n = int(rng.integers(1, 3000))
        cap = int(rng.integers(1, 600))
        got = largest_aligned_divisor(n, cap)
        want = min(cap, n)
        while n % want:
            want -= 1                      # the replaced O(n) loop
        assert got == want, (n, cap)


# -- registry completeness (CI smoke: every kernel exposes a space) --------------

def test_every_kernel_exposes_a_tunable_space():
    names = ktune.list_kernels()
    assert set(names) >= {"flash_attention", "decode_attention",
                          "mamba_scan", "mamba_scan_bwd", "rwkv6_wkv",
                          "rwkv6_wkv_bwd", "dna_automaton"}
    for name in names:
        spec = ktune.get_kernel(name)
        space = spec.space(spec.smoke_shape)
        # every space must be combinatorially interesting: the paper's
        # search strategies degenerate on near-singleton spaces
        assert space.size() >= 64, (name, space.size())
        default = spec.default_config(space, spec.smoke_shape)
        assert spec.validate(default, spec.smoke_shape) is None, name
        # the spaces deliberately contain invalid candidates: the
        # evaluator must be able to reject at least one for free
        invalid = [cfg for cfg in space.enumerate()
                   if spec.validate(cfg, spec.smoke_shape) is not None]
        assert invalid, f"{name}: space has no invalid candidates to gate"


def test_unknown_kernel_raises():
    with pytest.raises(ValueError, match="unknown kernel"):
        ktune.get_kernel("nope")


# -- timed parity evaluator ------------------------------------------------------

@pytest.mark.parametrize("name", ["flash_attention", "decode_attention",
                                  "mamba_scan", "rwkv6_wkv",
                                  "dna_automaton"])
def test_default_and_random_config_parity(name):
    """Every kernel: default + a random valid config run to numerical
    parity with ref.py (a finite timer score IS the parity assertion)."""
    spec = ktune.get_kernel(name)
    meta = spec.smoke_shape
    space = spec.space(meta)
    timer = KernelTimer(spec, meta, "float32", repeats=1, seed=0)
    assert np.isfinite(timer(spec.default_config(space, meta)))
    rng = np.random.default_rng(1)
    for _ in range(50):
        cfg = space.random(rng)
        if spec.validate(cfg, meta) is None:
            assert np.isfinite(timer(cfg)), cfg
            break


@pytest.mark.parametrize("name,shape,dtype", [
    ("flash_attention", {"tq": 256, "tk": 256, "hd": 64}, jnp.bfloat16),
    ("decode_attention", {"s": 256, "hd": 64}, jnp.bfloat16),
    ("mamba_scan", {"t": 128, "di": 384}, jnp.float32),
    ("rwkv6_wkv", {"t": 96, "hd": 32}, jnp.float32),
    ("dna_automaton", {"t": 8192}, jnp.uint8),
])
def test_parity_across_shape_dtype_grid(name, shape, dtype):
    spec = ktune.get_kernel(name)
    meta = dict(spec.smoke_shape, **shape)
    space = spec.space(meta)
    timer = KernelTimer(spec, meta, dtype, repeats=1, seed=2)
    assert np.isfinite(timer(spec.default_config(space, meta)))


@pytest.mark.parametrize("name,cfg,meta,field", [
    ("flash_attention", {"block_q": 12, "block_k": 128, "dims": "parallel"},
     {"bh": 2, "tq": 120, "tk": 128, "hd": 64, "causal": True}, "block_q"),
    ("decode_attention", {"block_s": 36, "splits": 1, "dims": "parallel"},
     {"b": 1, "kv": 2, "rep": 4, "hd": 128, "s": 72}, "block_s"),
    ("mamba_scan", {"block_d": 64, "chunk": 64, "lanes": 0, "unroll": 1,
                    "dims": "parallel"},
     {"bt": 1, "t": 128, "di": 512, "s": 16}, "block_d"),
    ("mamba_scan_bwd", {"block_d": 256, "chunk": 4, "dims": "parallel"},
     {"bt": 1, "t": 128, "di": 512, "s": 16}, "chunk"),
    ("rwkv6_wkv", {"chunk": 4, "lanes": 0, "block_h": 1,
                   "dims": "parallel"},
     {"b": 1, "t": 64, "h": 2, "hd": 64}, "chunk"),
    ("dna_automaton", {"map_chunk": 64, "count_chunk": 256,
                       "dims": "parallel"}, {"t": 4096, "s": 7}, "map_chunk"),
])
def test_validity_rejects_blocks_off_the_tpu_tile_grid(name, cfg, meta, field):
    """Blocks that divide their extent but break Mosaic's (8, 128)
    rule are refused before any launch; the whole extent is legal."""
    spec = ktune.get_kernel(name)
    reason = spec.validate(cfg, meta)
    assert reason is not None and "tile grid" in reason, reason
    assert reason.startswith(field)


def test_default_config_launch_failure_is_an_error():
    """A non-default candidate that fails to launch scores inf; the
    space's own default failing raises."""
    import dataclasses

    spec = ktune.get_kernel("flash_attention")
    meta = spec.smoke_shape
    default = spec.default_config(spec.space(meta), meta)

    def run(cfg, inputs, interpret):
        raise RuntimeError("Mosaic refused the kernel")

    broken = dataclasses.replace(spec, run=run)
    timer = KernelTimer(broken, meta, "float32", repeats=1)
    other = dict(default, block_q=64)
    assert spec.validate(other, meta) is None
    assert timer(other) == float("inf")
    assert "launch failed" in timer.rejected[timer._key(other)]
    with pytest.raises(RuntimeError, match="default launch config"):
        timer(default)


def test_invalid_config_scores_inf_without_measuring():
    spec = ktune.get_kernel("flash_attention")
    meta = spec.smoke_shape                      # tq = tk = 128
    timer = KernelTimer(spec, meta, "float32", repeats=1)
    bad = {"block_q": 1024, "block_k": 128, "dims": "parallel"}
    assert timer(bad) == float("inf")
    assert timer.n_measured == 0
    assert "exceed" in next(iter(timer.rejected.values()))


# -- tune + cache round trip -----------------------------------------------------

def test_cache_round_trip_zero_measurements(tmp_path):
    store = TuningStore(tmp_path / "kernels.json", devices="pinned")
    first = ktune.tune_kernel("rwkv6_wkv", strategy="random", iterations=3,
                              smoke=True, repeats=1, seed=0, store=store)
    assert first.n_measured > 0
    assert not first.result.from_cache
    again = ktune.tune_kernel("rwkv6_wkv", strategy="random", iterations=3,
                              smoke=True, repeats=1, seed=0, store=store)
    assert again.result.from_cache
    assert again.n_measured == 0                 # the acceptance bar
    assert again.best_config == first.best_config


def test_saml_tunes_within_budget(tmp_path):
    store = TuningStore(tmp_path / "kernels.json", devices="pinned")
    out = ktune.tune_kernel("dna_automaton", strategy="saml",
                            iterations=60, smoke=True, repeats=1, seed=0,
                            store=store)
    spec = ktune.get_kernel("dna_automaton")
    assert spec.validate(out.best_config, out.shape) is None
    assert np.isfinite(out.best_time())
    # surrogate training + winner re-score stay a small fraction of the
    # space (the smoke space is tiny, so just bound the absolute count)
    assert out.n_measured <= max(5, int(0.10 * out.space_size) + 1)
    assert out.result.n_training_experiments > 0


def test_best_record_spans_strategies(tmp_path):
    store = TuningStore(tmp_path / "kernels.json", devices="pinned")
    ktune.tune_kernel("rwkv6_wkv", strategy="random", iterations=2,
                      smoke=True, repeats=1, seed=0, store=store)
    ktune.tune_kernel("rwkv6_wkv", strategy="hillclimb", iterations=2,
                      smoke=True, repeats=1, seed=1, store=store)
    spec = ktune.get_kernel("rwkv6_wkv")
    space = spec.space(spec.smoke_shape)
    workload = ktune.kernel_workload("rwkv6_wkv", spec.smoke_shape,
                                     "float32")
    best = store.best_record(space, workload)
    assert best is not None
    by_strategy = [store.lookup(space, workload, s)
                   for s in ("RANDOM", "HILLCLIMB")]
    assert best.best_energy_measured == min(
        r.best_energy_measured for r in by_strategy if r is not None)


def test_space_change_forces_retune(tmp_path):
    """Editing a kernel's ConfigSpace must invalidate its cached tune:
    the store key hashes the space fingerprint, so the narrowed space
    misses and fresh measurements happen (no stale winner is served)."""
    import dataclasses

    from repro.core.space import ConfigSpace, Param

    store = TuningStore(tmp_path / "kernels.json", devices="pinned")
    first = ktune.tune_kernel("rwkv6_wkv", strategy="random", iterations=2,
                              smoke=True, repeats=1, seed=0, store=store)
    assert first.n_measured > 0
    again = ktune.tune_kernel("rwkv6_wkv", strategy="random", iterations=2,
                              smoke=True, repeats=1, seed=0, store=store)
    assert again.result.from_cache and again.n_measured == 0

    spec = ktune.get_kernel("rwkv6_wkv")

    def narrowed(meta):
        space = spec.space_fn(meta)
        return ConfigSpace([
            Param(p.name, p.values[:-1], ordinal=p.ordinal)
            if p.name == "chunk" else p for p in space.params])

    try:
        ktune.register_kernel(dataclasses.replace(spec, space_fn=narrowed))
        redo = ktune.tune_kernel("rwkv6_wkv", strategy="random",
                                 iterations=2, smoke=True, repeats=1,
                                 seed=0, store=store)
        assert not redo.result.from_cache
        assert redo.n_measured > 0
    finally:
        ktune.register_kernel(spec)


# -- the ops tuned= path ---------------------------------------------------------

def test_tuned_true_falls_back_gracefully(tmp_path, tuned_path_disabled):
    """tuned=True with an empty store must run the defaults, bit-for-bit."""
    from repro.kernels.flash_attention import ops as fa_ops

    ktune.configure(str(tmp_path / "empty.json"), enabled=False)
    rng = np.random.default_rng(0)
    q, k, v = (jnp.asarray(rng.standard_normal((1, 128, 2, 32)),
                           jnp.float32) for _ in range(3))
    base = fa_ops.flash_attention(q, k, v, causal=True)
    tuned = fa_ops.flash_attention(q, k, v, causal=True, tuned=True)
    np.testing.assert_array_equal(np.asarray(base), np.asarray(tuned))


def test_tuned_path_resolves_recorded_config(tmp_path, tuned_path_disabled):
    """After tuning, ops called with the global enable resolve the cached
    best config (zero measurements) and still match ref.py."""
    from repro.kernels.dna_automaton import ops as dna_ops
    from repro.kernels.dna_automaton import ref as dna_ref

    spec = ktune.get_kernel("dna_automaton")
    meta = spec.smoke_shape
    store = TuningStore(tmp_path / "kernels.json")    # live topology: the
    out = ktune.tune_kernel("dna_automaton", strategy="random",
                            iterations=4, smoke=True, repeats=1, seed=0,
                            store=store)              # resolver uses it too
    ktune.configure(store)
    resolved = ktune.resolve_config(
        "dna_automaton", {"t": meta["t"], "s": meta["s"]}, jnp.uint8)
    assert resolved == out.best_config

    table, accept = dna_ops.build_motif_dfa("ACGTAC")
    rng = np.random.default_rng(3)
    text = jnp.asarray(rng.integers(0, 4, meta["t"]).astype(np.uint8))
    got = int(dna_ops.fa_match(text, table, accept))   # tuned=None: global
    want = int(dna_ref.fa_match_ref(text, jnp.asarray(table),
                                    jnp.asarray(accept))[0])
    assert got == want


def test_hand_edited_stale_config_is_dropped(tmp_path, tuned_path_disabled):
    """A store entry whose best_config is no longer a point of the
    current space (hand-edited file, renamed launch param) must resolve
    to {} — the ops layer keeps its defaults rather than crashing."""
    import json

    path = tmp_path / "kernels.json"
    store = TuningStore(path, devices="pinned")
    out = ktune.tune_kernel("rwkv6_wkv", strategy="random", iterations=2,
                            smoke=True, repeats=1, seed=0, store=store)
    spec = ktune.get_kernel("rwkv6_wkv")
    meta = dict(spec.smoke_shape)
    ktune.configure(TuningStore(path, devices="pinned"), enabled=False)
    assert ktune.resolve_config("rwkv6_wkv", meta,
                                jnp.float32) == out.best_config

    # The store writes a checksummed {"checksum", "entries"} envelope;
    # hand-edit the entries and write back the legacy flat layout (which
    # the loader still accepts) to model an old hand-maintained file.
    data = json.loads(path.read_text())["entries"]
    for entry in data.values():
        for report in entry["reports"].values():
            report["best_config"]["chunk"] = 999      # out of the domain
    path.write_text(json.dumps(data))
    ktune.configure(TuningStore(path, devices="pinned"), enabled=False)
    assert ktune.resolve_config("rwkv6_wkv", meta, jnp.float32) == {}
