"""Serving driver: batched prefill -> decode loop with KV caches.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen2.5-3b --smoke \
        --batch 4 --prompt-len 32 --gen 16

``--stream`` switches to the online runtime: request batches flow
through ``repro.runtime.StreamingPipeline``, each batch chunk-scheduled
across device groups (``--slow N`` reserves the last N devices as a
second group), and the EWMA controller adapts the split per request mix.

``--tuned-kernels STORE`` enables the kernel-autotuning fast path: the
Pallas kernels resolve their cached best launch parameters (tuned via
``repro.tune.kernels`` / ``benchmarks/bench_kernels.py``) per traced
shape, with zero measurements at serve time.

Observability (``repro.obs``): ``--trace-out`` / ``--journal-out`` /
``--metrics-out`` record a ``--stream`` run — a Chrome-loadable span
trace, the decision journal (JSONL), and an ``obs_summary.json``.
``--fault-plan "kill:0@3,slow:1@9:4"`` replays a scripted failure drill
against the simulated serial-device groups on a ``VirtualClock`` (no
model build, deterministic timestamps) — the CI obs-smoke job validates
its artifacts against ``docs/obs_schema.json``.

``--serve-requests N`` switches to the request-level serving engine
(``repro.serve``): N requests from a deterministic arrival source flow
through SLO-aware admission and the continuous batcher into the
chunked scheduler, with per-request completion records.  With
``--sim-serve`` or ``--fault-plan`` the engine runs the deterministic
sim rig (``VirtualClock``, no model build — the CI serve-smoke drill);
otherwise real prefill+decode serves each formed batch.
``--tune-batcher`` tunes the batcher knobs through ``TuningSession``
(persisted in ``--batcher-store``) before serving; ``docs/serving.md``
documents the policies.

Crash durability (``runtime.checkpoint``; sim rig only): ``--wal PATH``
appends every admit/retire/step to a write-ahead request log and
``--snapshot PATH`` checkpoints the engine's soft state; after a crash
(scripted via ``--fault-plan 'crash:0@N'``, raising by default or a
real ``SIGKILL`` with ``--crash-sigkill``) the same command plus
``--resume`` replays unretired requests and finishes the run with every
admitted request accounted — the CI recover-smoke drill;
``docs/resilience.md`` documents the protocol.
"""

from __future__ import annotations

import argparse
import time
from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from .. import configs
from ..core.hetero import DeviceGroup
from ..dist.api import use_rules
from ..dist.sharding import ShardingConfig
from ..models import build_model
from ..obs import as_observer, get_logger
from .compile_cache import enable_compile_cache
from .mesh import make_host_mesh
from . import steps

log = get_logger("repro.serve")


def serving_model(cfg):
    """The model as served: weights held in the compute dtype.

    Training keeps f32 master weights; serving never needs them, and
    initialising straight in the compute dtype (bf16) means no f32 copy
    of the weights ever exists on a device."""
    return build_model(replace(cfg, param_dtype=cfg.compute_dtype))


def request_prompt(vocab_size: int, seed: int, rid: int, rows: int,
                   prompt_len: int) -> np.ndarray:
    """The synthetic prompt of request ``rid``: ``(rows, prompt_len)``
    int32 tokens that depend only on ``(seed, rid)``, so the same
    request carries the same prompt however it is batched."""
    return np.random.default_rng((seed, rid)).integers(
        0, vocab_size, (rows, prompt_len), dtype=np.int32)


def serve_session(cfg, *, batch: int, prompt_len: int, gen: int,
                  scfg: ShardingConfig | None = None, mesh=None,
                  seed: int = 0, greedy: bool = True) -> dict:
    """Prefill a random prompt batch, then decode ``gen`` tokens."""
    mesh = mesh or make_host_mesh()
    scfg = scfg or ShardingConfig(
        data_axes=mesh.axis_names[:1], model_axes=(), fsdp_axes=(),
        kv_shard="none", remat=False)
    model = serving_model(cfg)
    max_len = prompt_len + gen
    rng = np.random.default_rng(seed)

    with jax.set_mesh(mesh), use_rules(scfg.rules(mesh)):
        params = jax.jit(model.init)(jax.random.PRNGKey(seed))
        tokens = jnp.asarray(rng.integers(
            0, cfg.vocab_size, (batch, prompt_len)), jnp.int32)

        t0 = time.time()
        if cfg.encdec:
            frames = jnp.asarray(rng.standard_normal(
                (batch, prompt_len, cfg.d_model)), jnp.float32) * 0.02
            state = model.init_decode_state(batch, max_len,
                                            cross_len=prompt_len)
            state = jax.jit(model.prefill_cross)(params, state, frames)
            start_pos = 0
            last_tok = jnp.zeros((batch, 1), jnp.int32)
        else:
            logits, state = jax.jit(
                lambda p, t: model.prefill(p, t, max_len=max_len)
            )(params, tokens)
            start_pos = prompt_len
            last_tok = jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32)
        t_prefill = time.time() - t0

        decode = jax.jit(model.decode_step, donate_argnums=(1,))
        out_tokens = [last_tok]
        t0 = time.time()
        key = jax.random.PRNGKey(seed)
        for i in range(gen - 1):
            pos = jnp.int32(start_pos + i)
            logits, state = decode(params, state, last_tok, pos)
            if greedy:
                last_tok = jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32)
            else:
                key, k = jax.random.split(key)
                last_tok = jax.random.categorical(
                    k, logits[:, -1])[:, None].astype(jnp.int32)
            out_tokens.append(last_tok)
        generated = jnp.concatenate(out_tokens, axis=1)
        generated.block_until_ready()
        t_decode = time.time() - t0

    return {
        "generated": np.asarray(generated),
        "prefill_s": t_prefill,
        "decode_s": t_decode,
        "tokens_per_s": batch * (gen - 1) / max(t_decode, 1e-9),
    }


def compile_decode(decode, params, state, tokens, pos, observer=None):
    """Compile the jitted decode step for these arguments (arrays, or
    ``ShapeDtypeStruct``s with shardings) ahead of its first call, and
    return the compiled program.

    Called with the arrays of a chunk's first decode call, the jitted
    ``decode`` then runs this program without compiling again.  The
    program's temporary bytes, which a decode that copies its cache
    needs a cache's worth of, go to ``observer``'s metrics (gauge
    ``decode.temp_bytes``, the largest over the shapes compiled) and to
    the log, once per chunk shape; nothing is recorded per call.  So do
    the bytes one row of each kind of decode state holds at this shape
    (gauges ``decode.state_bytes.kv``, attention's K/V cache, and
    ``decode.state_bytes.ssm``, the recurrent layers' state).
    """
    compiled = decode.lower(params, state, tokens, pos).compile()
    temp = compiled.memory_analysis().temp_size_in_bytes
    rows = tokens.shape[0]
    held = {"kv": 0, "ssm": 0}
    for path, leaf in jax.tree_util.tree_leaves_with_path(state):
        kind = "kv" if path[-1].key in ("k", "v") else "ssm"
        held[kind] += leaf.size * jnp.dtype(leaf.dtype).itemsize // rows
    obs = as_observer(observer)
    if obs is not None:
        gauge = obs.metrics.gauge("decode.temp_bytes")
        gauge.set(max(gauge.value or 0, temp))
        for kind, n in held.items():
            obs.metrics.gauge(f"decode.state_bytes.{kind}").set(n)
    log.info(f"decode program for {rows} rows: {temp} temporary bytes; "
             f"state per row: {held['kv']} bytes of K/V, {held['ssm']} of "
             "recurrent state")
    return compiled


def _stream_step_builder(model, *, prompt_len: int, gen: int, seed: int,
                         observer=None):
    """Per-group prefill+decode step factory shared by ``serve_stream``,
    ``serve_requests`` and the split tuner (same jitted functions, same
    chunk contract).

    Each group's replica of the weights is initialised straight onto the
    group's devices, and each chunk's tokens are placed there too, split
    over the group's data axis where the rows divide.  The first chunk of
    each shape compiles its decode ahead of the first call
    (:func:`compile_decode`, recording into ``observer``).
    ``fn(chunk)`` returns the greedy tokens ``(rows, gen)`` and the
    prefill's last-position logits ``(rows, vocab)``; ``fn.params`` is
    the group's replica."""
    max_len = prompt_len + gen

    def step_builder(group: DeviceGroup):
        mesh = group.mesh()
        scfg = ShardingConfig(data_axes=mesh.axis_names[:1], model_axes=(),
                              fsdp_axes=(), kv_shard="none", remat=False)
        rules = scfg.rules(mesh)
        with jax.set_mesh(mesh), use_rules(rules):
            params = jax.jit(model.init,
                             out_shardings=NamedSharding(mesh, P()))(
                jax.random.PRNGKey(seed))
        prefill = jax.jit(lambda p, t: model.prefill(p, t, max_len=max_len))
        decode = jax.jit(model.decode_step, donate_argnums=(1,))
        compiled: set[tuple] = set()        # chunk shapes decode compiled for

        def fn(chunk):
            with jax.profiler.TraceAnnotation("step.prefill"):
                tokens = chunk["tokens"]
                tokens = jax.device_put(tokens, NamedSharding(
                    mesh, P(rules.spec_dim("batch", tokens.shape[0]))))
                with jax.set_mesh(mesh), use_rules(rules):
                    logits, state = prefill(params, tokens)
                    first = logits[:, -1]
                    last = jnp.argmax(logits[:, -1:],
                                      axis=-1).astype(jnp.int32)
            with jax.set_mesh(mesh), use_rules(rules):
                if tokens.shape not in compiled:
                    compile_decode(decode, params, state, last,
                                   jnp.int32(prompt_len), observer)
                    compiled.add(tokens.shape)
                outs = [last]
                with jax.profiler.TraceAnnotation("step.decode"):
                    for i in range(gen - 1):
                        logits, state = decode(params, state, last,
                                               jnp.int32(prompt_len + i))
                        last = jnp.argmax(logits[:, -1:],
                                          axis=-1).astype(jnp.int32)
                        outs.append(last)
                return {"tokens": jnp.concatenate(outs, axis=1),
                        "logits": first}
        fn.params = params
        return fn

    return step_builder


def _memoize_per_group(step_builder):
    """Cache the per-group step closures (params init + jitted
    prefill/decode) so a builder shared between ``tune_stream_split``
    and ``serve_stream`` compiles each group's functions exactly once."""
    cache: dict[int, object] = {}

    def memoized(group: DeviceGroup):
        key = id(group)
        if key not in cache:
            cache[key] = step_builder(group)
        return cache[key]
    return memoized


def tune_stream_split(cfg, *, groups: list[DeviceGroup], batch: int = 8,
                      prompt_len: int = 16, gen: int = 8, seed: int = 0,
                      strategy: str = "sam", iterations: int = 10,
                      store=None, chunks_per_group: int = 2,
                      row_quantum: int = 2, model=None, step_builder=None):
    """Offline-tune the initial two-group split through ``repro.tune``.

    The paper's loop at serve time: the config space is the fraction of
    each request batch handed to the first group, one measurement is a
    chunk-scheduled dispatch (rebalance off) of a representative batch,
    and any registered strategy searches it.  ``store`` caches the tuned
    split per (batch shape x group topology) workload signature, so a
    serving session on a known workload starts at the tuned split with
    zero extra measurements.  Returns shares for the controller.
    """
    from ..core.space import ConfigSpace, Param
    from ..runtime import ChunkedScheduler, EwmaController
    from ..tune import TuningSession

    if len(groups) != 2:
        raise ValueError("tune_stream_split needs exactly two device groups")
    if step_builder is None:
        model = model if model is not None else serving_model(cfg)
        step_builder = _stream_step_builder(model, prompt_len=prompt_len,
                                            gen=gen, seed=seed)
    rng = np.random.default_rng(seed)
    sample = {"tokens": jnp.asarray(
        rng.integers(0, cfg.vocab_size, (batch, prompt_len)), jnp.int32)}
    controller = EwmaController(2)
    sched = ChunkedScheduler(
        step_builder, groups, controller=controller,
        chunks_per_group=chunks_per_group, row_quantum=row_quantum)
    space = ConfigSpace([Param("fraction", tuple(range(10, 100, 10)))])

    def measure(cfg_point):
        f = cfg_point["fraction"] / 100.0
        controller.shares = np.asarray([f, 1.0 - f])
        rec = sched.step(sample, rebalance=False)
        return {"time": rec["t_step"], "t_host": rec["t_group"][0],
                "t_device": rec["t_group"][1]}

    workload = None
    if store is not None:
        workload = {"batch": (batch, prompt_len, gen), "arch": cfg.name,
                    "groups": [(g.name, len(g.devices), g.work_multiplier)
                               for g in groups]}
    session = TuningSession(space, evaluator=measure, store=store,
                            workload=workload)
    result = session.run(strategy, iterations=iterations, seed=seed)
    f = result.best_config["fraction"] / 100.0
    return np.asarray([f, 1.0 - f]), result


def serve_stream(cfg, *, groups: list[DeviceGroup], n_batches: int = 4,
                 batch: int = 8, prompt_len: int = 16, gen: int = 8,
                 seed: int = 0, chunks_per_group: int = 2,
                 row_quantum: int = 2, controller=None,
                 initial_shares=None, model=None,
                 step_builder=None, guard=None, observer=None,
                 clock=None, injector=None) -> dict:
    """Adaptive serving: chunk-schedule request batches across groups.

    Each group holds its own (replicated) copy of the params and runs
    full prefill+decode for the request rows it is handed; the
    ``StreamingPipeline``'s EWMA controller moves rows between groups as
    measured per-chunk times come in, so the split tracks the live
    request mix and relative group speed.  Decoder-only models.
    ``row_quantum`` coarsens chunk sizes (prefill/decode re-jit per
    distinct chunk shape, so coarse quanta keep the compiled-shape set
    small while the split drifts).  ``initial_shares`` (e.g. from
    ``tune_stream_split``) starts the controller at a tuned split
    instead of uniform.  ``guard`` (``True`` or a preconfigured
    ``repro.runtime.ServeGuard``) adds the kill-switch guardrail: if the
    online trajectory regresses, the split pins to the last known-good
    static configuration until a cool-down probe passes
    (``docs/resilience.md``).

    ``observer`` (``repro.obs.Observer``) records the run; ``clock``
    passes through to the scheduler (share it with the observer and a
    sim ``step_builder`` for deterministic traces); ``injector`` (a
    ``repro.runtime.FaultInjector``) is ticked once per batch and
    attached so recover events restore membership — the fault-drill
    surface behind ``--fault-plan``.
    """
    from ..runtime import EwmaController, StreamingPipeline

    if cfg.encdec:
        raise ValueError("serve_stream supports decoder-only models")
    n_devices = sum(len(g.devices) for g in groups)
    if batch < n_devices:
        raise ValueError(
            f"--batch {batch} is smaller than one request per device "
            f"({n_devices}); raise --batch or use fewer devices/groups")
    if step_builder is None:
        model = model if model is not None else serving_model(cfg)
        step_builder = _stream_step_builder(model, prompt_len=prompt_len,
                                            gen=gen, seed=seed,
                                            observer=observer)
    if controller is None and initial_shares is not None:
        controller = EwmaController(len(groups),
                                    shares=np.asarray(initial_shares))

    pipeline = StreamingPipeline(
        step_builder, groups, chunks_per_group=chunks_per_group,
        row_quantum=row_quantum, controller=controller, guard=guard,
        clock=clock, observer=observer)
    rng = np.random.default_rng(seed)
    batches = [{"tokens": jnp.asarray(
        rng.integers(0, cfg.vocab_size, (batch, prompt_len)), jnp.int32)}
        for _ in range(n_batches)]
    if injector is not None:
        # route recover events through the membership surface, and feed
        # the scripted plan one scheduler step at a time
        injector.attach(pipeline.guard if pipeline.guard is not None
                        else pipeline.scheduler)
        records = []
        for b in batches:
            injector.tick()
            records.extend(pipeline.run([b]))
    else:
        records = pipeline.run(batches)
    summary = pipeline.summary()
    summary["tokens_per_s_mean"] = summary["rows_per_s_mean"] * gen
    return {"records": records, "summary": summary}


def serve_requests(cfg, *, groups: list[DeviceGroup], n_requests: int,
                   rate_rps: float, prompt_len: int, gen: int,
                   seed: int = 0, batcher_config=None, guard: bool = False,
                   observer=None, row_quantum: int = 1, classes=None,
                   model=None, step_builder=None) -> dict:
    """Request-level serving on real devices: the ``repro.serve`` engine
    over a prefill+decode step builder.

    Every request asks for rows of one ``(prompt_len, gen)`` shape; its
    prompt is :func:`request_prompt` of its rid.  The arrival process
    and priorities come from the source's default mix, and the SLOs
    from ``classes`` (``serve.RequestClass`` tuples; the source's
    default mix when None).  The continuous batcher re-forms a
    scheduler batch per step from whatever is queued, and the chunked
    scheduler splits each batch across ``groups``.  Arrival waits are
    real ``time.sleep`` — for the deterministic virtual-clock rig use
    ``repro.serve.make_sim_engine`` (the ``--sim-serve`` /
    ``--fault-plan`` path).

    Before the first arrival, one unrebalanced scheduler step per batch
    size the batcher can form compiles every chunk shape, so no
    compilation is billed to a request or to the admission's service
    estimate.  Returns the engine summary, the per-request records, the
    scheduler (its ``history`` holds each step's group failures) and
    the errors of steps on which every group failed.
    """
    from ..runtime import ChunkedScheduler, ServeGuard
    from ..serve import (AdmissionController, BatcherConfig,
                         ContinuousBatcher, RequestSource, ServeEngine,
                         SloPolicy)

    if step_builder is None:
        model = model if model is not None else serving_model(cfg)
        step_builder = _memoize_per_group(_stream_step_builder(
            model, prompt_len=prompt_len, gen=gen, seed=seed,
            observer=observer))
    rows_choices = (1, 2, 4)

    def payload_fn(fb):
        tokens = np.zeros((fb.padded_rows, prompt_len), np.int32)
        for (lo, rows), req in zip(fb.spans, fb.requests):
            tokens[lo:lo + rows] = request_prompt(
                cfg.vocab_size, seed, req.rid, rows, prompt_len)
        return {"tokens": tokens}

    scheduler = ChunkedScheduler(step_builder, groups,
                                 row_quantum=max(row_quantum, 1),
                                 observer=observer)
    bcfg = batcher_config or BatcherConfig()
    align = sum(len(g.devices) for g in groups) * scheduler.row_quantum
    most = max(bcfg.max_batch_rows, max(rows_choices))
    for rows in range(align, -(-most // align) * align + 1, align):
        scheduler.step({"tokens": np.zeros((rows, prompt_len), np.int32)},
                       rebalance=False)
    # anchor arrivals on the engine's wall clock (the sim rig's
    # VirtualClock starts at 0; perf_counter does not)
    source = RequestSource(n_requests=n_requests, rate_rps=rate_rps,
                           seed=seed, shapes=((prompt_len, gen),),
                           rows_choices=rows_choices, classes=classes,
                           start=time.perf_counter())
    target = ServeGuard(scheduler) if guard else scheduler
    engine = ServeEngine(
        target, source=source,
        admission=AdmissionController(
            SloPolicy(max_queue_rows=bcfg.queue_depth_rows)),
        batcher=ContinuousBatcher(bcfg),
        payload_fn=payload_fn, observer=observer)
    summary = engine.run()
    summary["tokens_per_s"] = summary.get("goodput_rows_per_s", 0.0) * gen
    return {"summary": summary,
            "records": [r.record() for r in engine.done],
            "scheduler": scheduler, "step_errors": engine.step_errors}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2.5-3b", choices=configs.ARCH_NAMES)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--stream", action="store_true",
                    help="adaptive chunk-scheduled serving (repro.runtime)")
    ap.add_argument("--stream-batches", type=int, default=4)
    ap.add_argument("--slow", type=int, default=0,
                    help="reserve the last N devices as a second group")
    ap.add_argument("--tune-split", action="store_true",
                    help="tune the initial two-group split offline "
                    "(repro.tune session) before streaming")
    ap.add_argument("--tune-store", default=None,
                    help="TuningStore JSON path caching tuned splits "
                    "per workload signature")
    ap.add_argument("--tune-strategy", default="sam",
                    help="registered strategy for --tune-split "
                    "(see repro.tune.list_strategies())")
    ap.add_argument("--guard", action="store_true",
                    help="kill-switch guardrail: pin the last known-good "
                    "static split when the online controller regresses "
                    "(docs/resilience.md)")
    ap.add_argument("--guard-threshold", type=float, default=1.5,
                    help="trip when step time exceeds this multiple of "
                    "the rolling baseline")
    ap.add_argument("--guard-patience", type=int, default=5,
                    help="consecutive regressing steps before tripping")
    ap.add_argument("--attn-impl", default=None,
                    choices=["auto", "xla", "pallas"],
                    help="override the arch's mixer implementation "
                    "(pallas = the repro.kernels suite; interpret mode "
                    "on CPU)")
    ap.add_argument("--tuned-kernels", default=None, metavar="STORE",
                    help="kernel tuning store (JSON from "
                    "repro.tune.kernels.tune_kernel / bench_kernels.py); "
                    "Pallas kernels resolve their cached best launch "
                    "params for each traced shape, defaults on a miss")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="write a chrome://tracing span trace of the "
                    "--stream run (repro.obs)")
    ap.add_argument("--journal-out", default=None, metavar="PATH",
                    help="write the decision journal (JSONL) of the "
                    "--stream run")
    ap.add_argument("--metrics-out", default=None, metavar="PATH",
                    help="write obs_summary.json (counters, latency "
                    "percentiles, journal digest, provenance meta)")
    ap.add_argument("--log-level", default=None,
                    choices=["debug", "info", "warning", "error"],
                    help="filter the structured log (default info; also "
                    "REPRO_LOG_LEVEL)")
    ap.add_argument("--fault-plan", default=None, metavar="SPEC",
                    help="scripted failure drill for --stream, e.g. "
                    "'kill:0@3,slow:1@9:4' — runs against simulated "
                    "serial groups on a virtual clock (no model build); "
                    "see repro.runtime.parse_fault_plan")
    ap.add_argument("--sim-devices", type=int, default=8,
                    help="device count of the simulated groups under "
                    "--fault-plan")
    ap.add_argument("--serve-requests", type=int, default=None, metavar="N",
                    help="request-level serving (repro.serve): N requests "
                    "from a deterministic arrival source through admission "
                    "-> continuous batching -> the chunked scheduler")
    ap.add_argument("--request-rate", type=float, default=200.0,
                    help="offered load for --serve-requests (requests/s)")
    ap.add_argument("--serve-seed", type=int, default=0,
                    help="seed of the request arrival source")
    ap.add_argument("--sim-serve", action="store_true",
                    help="run --serve-requests on the deterministic sim "
                    "rig (VirtualClock, no model build) even without a "
                    "--fault-plan")
    ap.add_argument("--tune-batcher", action="store_true",
                    help="tune the continuous-batcher knobs through a "
                    "TuningSession (sim-rig evaluations) before serving")
    ap.add_argument("--batcher-store", default=None, metavar="PATH",
                    help="TuningStore JSON caching tuned batcher configs "
                    "per workload signature")
    ap.add_argument("--wal", default=None, metavar="PATH",
                    help="write-ahead request log for --serve-requests "
                    "(sim rig): every admit/retire/step is appended "
                    "before the engine proceeds, so a crashed run can "
                    "restart with --resume (docs/resilience.md)")
    ap.add_argument("--snapshot", default=None, metavar="PATH",
                    help="periodic checksummed snapshot of the engine's "
                    "soft state (controller shares, kill-switch, service "
                    "estimator) next to the --wal")
    ap.add_argument("--resume", action="store_true",
                    help="recover from --wal (and --snapshot if given) "
                    "before serving: unretired admitted requests replay "
                    "through admission, the clock and fault plan fast-"
                    "forward to the crash point")
    ap.add_argument("--crash-sigkill", action="store_true",
                    help="scripted crash faults (--fault-plan 'crash:0@N') "
                    "kill the process with SIGKILL instead of raising — "
                    "the real-process recovery drill")
    args = ap.parse_args()
    enable_compile_cache()
    from ..obs import Observer, configure
    if args.log_level:
        configure(level=args.log_level)
    cfg = configs.get(args.arch)
    if args.smoke:
        cfg = cfg.smoke()
    if args.attn_impl:
        from dataclasses import replace
        cfg = replace(cfg, attn_impl=args.attn_impl)
    if args.tuned_kernels:
        # every kernel op called with tuned=None (the models' default)
        # now resolves through this store at trace time — serving runs
        # the tuned launch parameters with zero extra measurements
        from ..tune import kernels as ktune
        ktune.configure(args.tuned_kernels)
    if args.serve_requests:
        from ..serve import BatcherConfig, make_sim_engine, tune_batcher
        observer = None
        journal_sink = None
        if args.trace_out or args.journal_out or args.metrics_out:
            observer = Observer()
            if args.journal_out:
                # stream every event as it happens (line-buffered +
                # per-event flush): a SIGKILL mid-run still leaves the
                # journal on disk up to the last decision.  save_journal
                # rewrites the same bytes at clean exit.
                from pathlib import Path
                Path(args.journal_out).parent.mkdir(parents=True,
                                                    exist_ok=True)
                journal_sink = open(args.journal_out, "w", buffering=1)
                observer.journal.sink = journal_sink
            configure(journal=observer.journal)
        sim = bool(args.fault_plan or args.sim_serve)
        if (args.wal or args.resume) and not sim:
            ap.error("--wal/--resume need the sim rig "
                     "(--sim-serve or --fault-plan)")
        if args.resume and not args.wal:
            ap.error("--resume needs --wal")
        bcfg = None
        if args.tune_batcher:
            # tune on the sim rig (cheap, deterministic); the store
            # re-serves a known workload with zero new measurements
            from ..runtime import TuningStore
            store = TuningStore(args.batcher_store) \
                if args.batcher_store else None
            workload = {"n_requests": args.serve_requests,
                        "rate_rps": args.request_rate,
                        "seed": args.serve_seed}

            def evaluate(cand):
                eng = make_sim_engine(n_requests=args.serve_requests,
                                      rate_rps=args.request_rate,
                                      seed=args.serve_seed,
                                      batcher_config=cand)
                s = eng.run()
                return {"time": s.get("e2e_p95", 10.0)
                        + 0.1 * s["shed_rate"],
                        "shed_rate": s["shed_rate"]}

            bcfg, tuned = tune_batcher(evaluate, store=store,
                                       workload=workload,
                                       observer=observer)
            log.info(f"tuned batcher: {bcfg} "
                     f"({tuned.n_experiments} measurements, "
                     f"{100 * tuned.experiments_fraction:.1f}% of space"
                     f"{', cached' if tuned.from_cache else ''})")
        if sim:
            from ..runtime.checkpoint import SimulatedCrash
            from ..runtime.simulate import parse_fault_plan
            plan = parse_fault_plan(args.fault_plan) \
                if args.fault_plan else None
            engine = make_sim_engine(
                n_requests=args.serve_requests,
                rate_rps=args.request_rate,
                seed=args.serve_seed, fault_plan=plan,
                guard=args.guard or bool(plan),
                batcher_config=bcfg, observer=observer,
                wal=args.wal, snapshot=args.snapshot,
                resume=args.resume,
                crash_mode="sigkill" if args.crash_sigkill else "raise")
            try:
                s = engine.run()
            except SimulatedCrash as exc:
                # scripted crash drill (crash_mode="raise"): the WAL and
                # streamed journal are already durable — flush what we
                # have and exit with the drill's sentinel code so CI can
                # assert the crash actually fired before the restart
                log.warning(f"simulated crash: {exc}",
                            steps=engine.steps)
                if engine.wal is not None:
                    engine.wal.sync()
                if journal_sink is not None:
                    journal_sink.close()
                raise SystemExit(17)
        else:
            devs = jax.devices()[:max(args.batch, 1)]
            if 0 < args.slow < len(devs):
                groups = [DeviceGroup("fast", devs[:-args.slow]),
                          DeviceGroup("slow", devs[-args.slow:])]
            else:
                groups = [DeviceGroup("all", devs)]
            out = serve_requests(
                cfg, groups=groups, n_requests=args.serve_requests,
                rate_rps=args.request_rate, prompt_len=args.prompt_len,
                gen=args.gen, seed=args.serve_seed, batcher_config=bcfg,
                guard=args.guard, observer=observer)
            s = out["summary"]
        replayed = (f"  {s['replayed']} replayed"
                    if s.get("replayed") else "")
        log.info(f"serve: {s['completed']}/{s['requests']} completed  "
                 f"{s['shed']} shed {s['shed_reasons']}  "
                 f"{s['retries']} retries{replayed}  "
                 f"e2e p99 {s.get('e2e_p99', float('nan')):.4f}s")
        if observer is not None:
            if args.trace_out:
                path = observer.save_trace(args.trace_out)
                log.info(f"trace: {path} ({len(observer.tracer)} events)")
            if args.journal_out:
                # close the stream first; save() rewrites the identical
                # bytes (plus anything the sink never saw on a non-crash
                # path — there is none with flush_every=1)
                if journal_sink is not None:
                    journal_sink.close()
                    observer.journal.sink = None
                path = observer.save_journal(args.journal_out)
                log.info(f"journal: {path} "
                         f"({len(observer.journal)} events)")
            if args.metrics_out:
                observer.write_summary(args.metrics_out,
                                       extra={"serve": s})
                log.info(f"metrics: {args.metrics_out}")
        return
    if args.stream:
        clock = injector = observer = None
        if args.fault_plan:
            if args.tune_split:
                ap.error("--fault-plan is a simulated drill; it cannot "
                         "combine with --tune-split")
            from ..runtime.simulate import (FakeDevice, FaultInjector,
                                            VirtualClock,
                                            make_serial_sim_builder,
                                            parse_fault_plan)
            # the drill runs against simulated serial groups on a
            # virtual clock: no model, no compile, and every timestamp
            # in the trace/journal is a deterministic simulated instant
            clock = VirtualClock()
            devs = [FakeDevice()
                    for _ in range(min(args.sim_devices,
                                       max(args.batch, 1)))]
        else:
            # the scheduler needs >= 1 request row per device: on small
            # --batch runs use only as many devices as there are rows
            devs = jax.devices()[:max(args.batch, 1)]
        if 0 < args.slow < len(devs):
            groups = [DeviceGroup("fast", devs[:-args.slow]),
                      DeviceGroup("slow", devs[-args.slow:])]
        else:
            groups = [DeviceGroup("all", devs)]
        if args.trace_out or args.journal_out or args.metrics_out:
            observer = Observer(clock=clock)
            # mirror every narrated line into the decision journal, so
            # the narration and the decisions land on one sequence
            configure(journal=observer.journal)
        initial_shares = None
        if args.fault_plan:
            injector = FaultInjector(parse_fault_plan(args.fault_plan),
                                     groups)
            builder = make_serial_sim_builder(1e-3, clock=clock,
                                              injector=injector)
        else:
            # one memoized builder: the split tuner and the serving
            # pipeline share per-group params init + jitted
            # prefill/decode
            builder = _memoize_per_group(_stream_step_builder(
                serving_model(cfg), prompt_len=args.prompt_len, gen=args.gen,
                seed=0))
        if args.tune_split:
            if len(groups) != 2:
                ap.error("--tune-split needs two groups (pass --slow N)")
            initial_shares, tuned = tune_stream_split(
                cfg, groups=groups, batch=args.batch,
                prompt_len=args.prompt_len, gen=args.gen,
                strategy=args.tune_strategy, store=args.tune_store,
                step_builder=builder)
            log.info(f"tuned split: {initial_shares.round(2)} "
                     f"({tuned.strategy}, {tuned.n_experiments} measurements"
                     f"{', cached' if tuned.from_cache else ''})")
        guard = None
        if args.guard:
            from ..runtime import KillSwitch, ServeGuard
            # last known-good fallback: the tuned split when we have one
            # (tuner-measured, the strongest prior); otherwise the guard
            # snapshots the best online split it observes
            guard = ServeGuard(
                None, switch=KillSwitch(threshold=args.guard_threshold,
                                        patience=args.guard_patience),
                fallback=initial_shares)
        out = serve_stream(cfg, groups=groups, n_batches=args.stream_batches,
                           batch=args.batch, prompt_len=args.prompt_len,
                           gen=args.gen, initial_shares=initial_shares,
                           step_builder=builder, guard=guard,
                           observer=observer, clock=clock,
                           injector=injector)
        s = out["summary"]
        guarded = f"  guard trips {s['guard_trips']}" if args.guard else ""
        log.info(f"stream: {s['batches']} batches  "
                 f"{s['tokens_per_s_mean']:.1f} tok/s  "
                 f"shares {s['shares_final']}{guarded}")
        if observer is not None:
            if args.trace_out:
                path = observer.save_trace(args.trace_out)
                log.info(f"trace: {path} ({len(observer.tracer)} events)")
            if args.journal_out:
                path = observer.save_journal(args.journal_out)
                log.info(f"journal: {path} "
                         f"({len(observer.journal)} events)")
            if args.metrics_out:
                observer.write_summary(args.metrics_out,
                                       extra={"stream": s})
                log.info(f"metrics: {args.metrics_out}")
        return
    out = serve_session(cfg, batch=args.batch, prompt_len=args.prompt_len,
                        gen=args.gen)
    log.info(f"prefill {out['prefill_s']:.2f}s  "
             f"decode {out['decode_s']:.2f}s  "
             f"{out['tokens_per_s']:.1f} tok/s")
    log.info(f"sample tokens: {out['generated'][0, :12]}")


if __name__ == "__main__":
    main()
