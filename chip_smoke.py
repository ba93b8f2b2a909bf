#!/usr/bin/env python3
"""Smoke check of the system's main path on TPU chips.

    python3 chip_smoke.py               # one chip: kernels, then serving
    python3 chip_smoke.py --four-chips  # unequal device groups on 4 chips

One chip.  Each Pallas kernel is compiled (``interpret=False``) and run
once at a real width and compared with its ``ref.py``, and so is the
backward pass of each kernel that has one.  Then qwen2.5-3b
at full width (36 layers, random bf16 weights from a seed) serves about
eight requests through ``launch.serve.serve_requests`` on one device
group.  Every request must complete with no shed and no retry, no group
may be demoted, and the served greedy tokens of a chunk must equal a
direct ``prefill`` + ``decode_step`` loop on the same prompts and
weights.

Four chips.  The same requests go through ``serve_requests`` on unequal
groups, ``fast`` (3 chips) and ``slow`` (1 chip), and are compared with
the same requests served in this process on one group of one chip.
Each chip must hold its group's replica, both groups must serve rows,
and every request's prefill logits must agree with the one-chip run.

The script runs in one process and starts none.  It exits nonzero and
prints no result line when JAX finds no TPU or any check fails; the
last line of a passing run is ``{"ok": true, "device": {...}}``.  Its
other numbers are smoke facts (what one run did), not benchmark
measurements.
"""

from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent

PROMPT_LEN, GEN = 512, 32
# eight requests in about two seconds, coalesced for up to a second so
# that batches fill: on four chips both groups then get request rows
N_REQUESTS, RATE_RPS, COALESCE_S = 8, 4.0, 1.0
SEED = 0
BF16_TOL, F32_TOL = 4 * 2.0 ** -7, 1e-3   # max |err| / max(1, max |ref|)
LOGITS_TOL = 6e-2                   # cross-topology bf16 forward passes


class SmokeFailure(Exception):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


class CompileLog:
    """Counts backend compilations and their seconds (JAX monitoring)."""

    def __init__(self):
        import jax
        self.programs, self.seconds = 0, 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, name, secs, **_):
        if name == "/jax/core/compile/backend_compile_duration":
            self.programs += 1
            self.seconds += secs


# -- kernels ---------------------------------------------------------------

def _max_err(out, want) -> tuple[float, float, bool]:
    import jax
    import numpy as np
    err, scale, finite = 0.0, 1.0, True
    for o, w in zip(jax.tree.leaves(out), jax.tree.leaves(want)):
        o, w = np.asarray(o, np.float64), np.asarray(w, np.float64)
        check(o.shape == w.shape, f"shape {o.shape} != reference {w.shape}")
        finite = finite and bool(np.isfinite(o).all())
        err = max(err, float(np.max(np.abs(o - w))))
        scale = max(scale, float(np.max(np.abs(w))))
    return err, scale, finite


def kernel_phase() -> None:
    """Each kernel once, compiled, at one real width, against ref.py."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels.decode_attention import ops as da, ref as da_ref
    from repro.kernels.dna_automaton import ops as dna, ref as dna_ref
    from repro.kernels.flash_attention import ops as fa, ref as fa_ref
    from repro.kernels.mamba_scan import ops as ms, ref as ms_ref
    from repro.kernels.rwkv6_wkv import ops as wkv, ref as wkv_ref

    rng = np.random.default_rng(SEED)

    def randn(*shape, dtype=jnp.float32, scale=1.0):
        return jnp.asarray(rng.standard_normal(shape) * scale, dtype)

    def decays(*shape):
        return jnp.asarray(1 / (1 + np.exp(-rng.standard_normal(shape) - 2)),
                           jnp.float32)

    # qwen2.5-3b attention: hd 128, 16 query heads over 2 KV heads
    q, k, v = (randn(1, 2048, 16, 128, dtype=jnp.bfloat16) for _ in range(3))
    qd = randn(4, 16, 128, dtype=jnp.bfloat16)
    kd, vd = (randn(4, 2048, 2, 128, dtype=jnp.bfloat16) for _ in range(2))
    # jamba-v0.1 mamba (d_inner 8192, d_state 16); rwkv6-1.6b (32 x 64)
    ms_args = (randn(1, 512, 8192), jnp.abs(randn(1, 512, 8192, scale=0.1)),
               -(jnp.abs(randn(8192, 16)) + 0.5), randn(1, 512, 16),
               randn(1, 512, 16), randn(8192))
    wkv_args = (randn(1, 512, 32, 64, scale=0.5),
                randn(1, 512, 32, 64, scale=0.5),
                randn(1, 512, 32, 64, scale=0.5), decays(1, 512, 32, 64),
                randn(32, 64, scale=0.1))
    table, accept = dna.build_motif_dfa("ACGTAC")
    text = rng.integers(0, 4, 1 << 20).astype(np.uint8)
    for pos in rng.integers(0, (1 << 20) - 6, 64):
        text[pos:pos + 6] = [0, 1, 2, 3, 0, 1]       # plant ACGTAC
    dna_args = (jnp.asarray(text), jnp.asarray(table), jnp.asarray(accept))

    def grads(fn, n):
        # d(0.5 * |first output|^2) / d(first n operands)
        def loss(*a):
            out = jax.tree.leaves(fn(*a))[0].astype(jnp.float32)
            return 0.5 * jnp.sum(out * out)
        return lambda *a: jax.grad(loss, argnums=tuple(range(n)))(*a)

    # (name, tol, operands, kernel, reference, differentiable operands)
    cases = [
        ("flash_attention", BF16_TOL, (q, k, v),
         lambda *a: fa.flash_attention(*a, causal=True, interpret=False),
         lambda *a: fa_ref.attention_ref(*a, causal=True), 3),
        ("decode_attention", BF16_TOL, (qd, kd, vd),
         lambda *a: da.decode_attention(*a, length=1500, interpret=False),
         lambda *a: da_ref.decode_attention_ref(*a, length=1500), 0),
        ("mamba_scan", F32_TOL, ms_args,
         lambda *a: ms.selective_scan(*a, interpret=False),
         ms_ref.selective_scan_ref, 6),
        ("rwkv6_wkv", F32_TOL, wkv_args,
         lambda *a: wkv.wkv6(*a, interpret=False), wkv_ref.wkv6_ref, 5),
        ("dna_automaton", 0.0, dna_args,
         lambda *a: dna.fa_match(*a, interpret=False),
         lambda *a: dna_ref.fa_match_ref(*a)[0], 0),
    ]
    for name, tol, args, kernel, ref, n_grad in cases:
        passes = [("", kernel, ref)]
        if n_grad:
            passes.append((" backward", grads(kernel, n_grad),
                           grads(ref, n_grad)))
        for which, fn, ref_fn in passes:
            out = jax.block_until_ready(jax.jit(fn)(*args))
            with jax.default_matmul_precision("highest"):
                want = jax.block_until_ready(jax.jit(ref_fn)(*args))
            err, scale, finite = _max_err(out, want)
            print(f"kernel {name}{which}: max abs err {err:.3e} (ref scale "
                  f"{scale:.3g}, tol {tol * scale:.3e})", flush=True)
            check(finite, f"kernel {name}{which}: non-finite output")
            check(err <= tol * scale, f"kernel {name}{which}: max abs err "
                  f"{err:.3e} exceeds {tol * scale:.3e}")
    check(int(out) >= 64, f"dna_automaton: {int(out)} matches, 64 planted")


# -- serving ---------------------------------------------------------------

def serve(cfg, model, groups, *, row_quantum: int) -> dict:
    """``serve_requests`` over ``groups``.  Returns the engine summary and
    records, the scheduler's step failures and demotions, each group's
    replica devices and parameters, the request rows each group served,
    and what was served: prompt row -> (greedy tokens, prefill logits),
    on the host."""
    import jax
    import numpy as np
    from repro.launch.serve import (_memoize_per_group, _stream_step_builder,
                                    serve_requests)
    from repro.serve import BatcherConfig, RequestClass

    builder = _stream_step_builder(model, prompt_len=PROMPT_LEN, gen=GEN,
                                   seed=SEED)
    params, chunks = {}, []

    def recording(group):
        fn = builder(group)
        params[group.name] = fn.params

        def run(chunk):
            res = fn(chunk)
            chunks.append((group.name, chunk["tokens"], res))
            return res
        return run

    align = sum(len(g.devices) for g in groups) * row_quantum
    out = serve_requests(
        cfg, groups=groups, n_requests=N_REQUESTS, rate_rps=RATE_RPS,
        prompt_len=PROMPT_LEN, gen=GEN, seed=SEED, row_quantum=row_quantum,
        batcher_config=BatcherConfig(max_batch_rows=align,
                                     coalesce_window_s=COALESCE_S),
        classes=(RequestClass("interactive", slo_s=20.0, priority=1,
                              weight=0.7),
                 RequestClass("batch", slo_s=60.0, priority=0, weight=0.3)),
        step_builder=_memoize_per_group(recording))
    scheduler = out["scheduler"]
    scheduler.close()
    # warm-up batches and alignment padding are all-zero prompt rows
    served, rows, probe = {}, {g.name: 0 for g in groups}, None
    for name, tokens, res in chunks:
        tokens = np.asarray(tokens)
        toks = np.asarray(res["tokens"])
        logits = np.asarray(res["logits"], np.float32)
        real = tokens.any(axis=1)
        rows[name] += int(real.sum())
        if probe is None and real[0]:
            probe = (tokens, toks)
        for i in np.flatnonzero(real):
            served[tokens[i].tobytes()] = (toks[i], logits[i])
    return {"summary": out["summary"], "records": out["records"],
            "step_errors": out["step_errors"],
            "failures": [rec["failures"] for rec in scheduler.history
                         if rec["failures"]],
            "demoted": [g.name for g, live in zip(groups, scheduler.live)
                        if not live],
            "replicas": {name: {d for leaf in jax.tree.leaves(p)
                                for d in leaf.sharding.device_set}
                         for name, p in params.items()},
            "params": params, "rows": rows, "served": served,
            "probe": probe}


def check_served(out: dict, label: str) -> None:
    s = out["summary"]
    print(f"{label}: {s['completed']}/{s['requests']} completed, "
          f"{s['shed']} shed {s['shed_reasons']}, {s['retries']} retries, "
          f"{s['steps']} steps, rows per group {out['rows']}", flush=True)
    for err in out["step_errors"]:
        print(f"{label}: step failed on every group: {err}", file=sys.stderr)
    for failure in out["failures"]:
        print(f"{label}: group failure: {failure}", file=sys.stderr)
    check(not out["step_errors"] and not out["failures"],
          f"{label}: scheduler reported failures")
    check(not out["demoted"], f"{label}: groups demoted: {out['demoted']}")
    check(s["requests"] == N_REQUESTS and s["completed"] == N_REQUESTS,
          f"{label}: {s['completed']} of {N_REQUESTS} requests completed")
    check(s["shed"] == 0 and s["retries"] == 0,
          f"{label}: {s['shed']} shed {s['shed_reasons']}, "
          f"{s['retries']} retries")


def request_rows(cfg, out: dict):
    """(rid, prompt rows) of every completed request."""
    from repro.launch.serve import request_prompt
    return [(r["rid"], request_prompt(cfg.vocab_size, SEED, r["rid"],
                                      r["rows"], PROMPT_LEN))
            for r in out["records"] if r["status"] == "completed"]


def greedy_reference(model, params, tokens):
    """Greedy tokens of a direct prefill + decode_step loop."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    prefill = jax.jit(lambda p, t: model.prefill(
        p, t, max_len=PROMPT_LEN + GEN))
    decode = jax.jit(model.decode_step, donate_argnums=(1,))
    logits, state = prefill(params, jnp.asarray(tokens))
    tok = jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32)
    outs = [tok]
    for i in range(GEN - 1):
        logits, state = decode(params, state, tok, jnp.int32(PROMPT_LEN + i))
        tok = jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32)
        outs.append(tok)
    return np.asarray(jnp.concatenate(outs, axis=1))


def serve_phase(cfg, device) -> int:
    """One group on one chip; returns the tokens served."""
    import numpy as np
    from repro.core.hetero import DeviceGroup
    from repro.launch.serve import serving_model

    model = serving_model(cfg)
    out = serve(cfg, model, [DeviceGroup("chip", [device])], row_quantum=4)
    check_served(out, "serve")
    for rid, rows in request_rows(cfg, out):
        check(all(r.tobytes() in out["served"] for r in rows),
              f"serve: request {rid}'s prompt never reached a device")
    check(out["probe"] is not None, "serve: no request chunk was served")
    prompts, served = out["probe"]
    want = greedy_reference(model, out["params"]["chip"], prompts)
    same = int((want == served).all(axis=1).sum())
    print(f"serve: greedy tokens equal the direct prefill+decode loop on "
          f"{same}/{len(want)} rows of a served chunk", flush=True)
    check(np.array_equal(want, served),
          "serve: served greedy tokens differ from the direct loop")
    return sum(r["rows"] for r in out["records"]) * GEN


def four_chip_phase(cfg, devices) -> int:
    """Unequal groups (3 + 1 chips) against one chip; returns tokens."""
    import numpy as np
    from repro.core.hetero import DeviceGroup
    from repro.launch.serve import serving_model

    model = serving_model(cfg)
    one = serve(cfg, model, [DeviceGroup("one", devices[:1])], row_quantum=4)
    check_served(one, "one-chip")
    ref = {rid: [one["served"][r.tobytes()] for r in rows]
           for rid, rows in request_rows(cfg, one)}
    del one
    gc.collect()

    groups = [DeviceGroup("fast", devices[:3]), DeviceGroup("slow",
                                                          devices[3:4])]
    out = serve(cfg, model, groups, row_quantum=3)
    check_served(out, "four-chip")
    for g in groups:
        held = out["replicas"][g.name]
        print(f"four-chip: group {g.name} replica on devices "
              f"{sorted(d.id for d in held)}", flush=True)
        check(held == set(g.devices),
              f"four-chip: group {g.name}'s replica is on {held}")
        check(out["rows"][g.name] > 0, f"four-chip: group {g.name} got "
              "no rows")
    worst, same, total = 0.0, 0, 0
    for rid, rows in request_rows(cfg, out):
        for r, (want_tok, want_logits) in zip(rows, ref[rid]):
            tok, logits = out["served"][r.tobytes()]
            scale = max(1.0, float(np.max(np.abs(want_logits))))
            worst = max(worst, float(np.max(np.abs(logits - want_logits)))
                        / scale)
            same += int(np.array_equal(tok, want_tok))
            total += 1
    print(f"four-chip: prefill logits vs one chip: max |err| / scale "
          f"{worst:.3e} (tol {LOGITS_TOL:.1e}); greedy tokens identical on "
          f"{same}/{total} rows", flush=True)
    check(worst <= LOGITS_TOL, "four-chip: prefill logits differ from the "
          "one-chip run")
    return sum(r["rows"] for r in out["records"]) * GEN


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="serve on groups of 3 + 1 chips against one chip, "
                    "and nothing else")
    args = ap.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        print(f"chip_smoke: no repro package under {ROOT / 'src'}",
              file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    from repro.launch.compile_cache import enable_compile_cache
    cache = enable_compile_cache()

    import jax
    devices = jax.devices()
    dev = devices[0]
    print(f"device: platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devices)}", flush=True)
    if dev.platform != "tpu":
        print("chip_smoke: JAX found no TPU", file=sys.stderr)
        return 1
    need = 4 if args.four_chips else 1
    if len(devices) < need:
        print(f"chip_smoke: needs {need} chips, found {len(devices)}",
              file=sys.stderr)
        return 1

    from repro import configs
    cfg = configs.get("qwen2.5-3b")          # full width: no .smoke()
    compiles = CompileLog()
    t0 = time.perf_counter()
    try:
        if args.four_chips:
            tokens = four_chip_phase(cfg, devices[:4])
        else:
            kernel_phase()
            tokens = serve_phase(cfg, dev)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    peak = max(d.memory_stats().get("peak_bytes_in_use", 0)
               for d in devices[:need])
    print(f"smoke facts (not benchmark numbers): {compiles.programs} "
          f"programs compiled in {compiles.seconds:.1f} s, "
          f"peak_bytes_in_use {peak}, tokens served {tokens}, "
          f"wall {time.perf_counter() - t0:.1f} s, compile cache {cache}",
          flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
