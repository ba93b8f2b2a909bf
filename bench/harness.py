"""One cell of ``BENCHMARK.json``: served, measured from the client's side,
and checked against the plain reference.

A cell names three data files, found by name:

* ``bench/configs/<config>.json``: the model's widths as run (keys as in
  its published ``config.json``), the registry architecture it selects,
  the deployment (device groups, batcher and row quantum) and, under
  ``reference``, the name of its architecture;
* ``bench/traffic/<mix>.json``: prompt and output lengths, rows per
  request, request classes and their SLOs, and the arrival process;
* ``bench/cells/<workload>.json``: the offered rate, how many served rows
  the check compares and the limits it holds them to, and where the
  traced stretch falls.

Through its configuration's ``reference`` a cell names a fourth file by
name, ``bench/models/<reference>.py``: the architecture, which maps the
file's published keys to the program's ``ArchConfig`` (``arch_config``)
and counts the operations and bytes of its calls (``counts``); the plain
reference the check compares with is ``bench/reference/<reference>.py``.
A new architecture brings both files and needs no change here.

Each metric is a reader of its own, ``bench/metrics/<metric>.py``, with
``read(run) -> float | None``.

The run drives the program's own serving entry, ``serve_requests``: the
open-loop Poisson source, admission, the continuous batcher and the
chunked scheduler, over the program's prefill + decode step builder.  The
harness wraps that step builder only to keep, without any host copy in
the window, the outputs of the chunks that carry the requests the check
will compare, and in a traced run to open and close the profiler at chunk
boundaries.
"""

from __future__ import annotations

import gc
import importlib.util
import json
import math
import shutil
import sys
import time
from dataclasses import dataclass, field
from functools import cached_property
from pathlib import Path

import numpy as np

from . import trace as trace_mod
from . import traffic as traffic_mod

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


class BenchError(Exception):
    """The cell cannot run as its files state."""


def _load(path: Path) -> dict:
    try:
        return json.loads(Path(path).read_text())
    except FileNotFoundError:
        raise BenchError(f"missing benchmark file {path}") from None


def _module(kind: str, name: str):
    """``bench/<kind>/<name>.py`` as a module."""
    path = BENCH / kind / f"{name}.py"
    if not path.is_file():
        raise BenchError(f"missing benchmark file {path}")
    spec = importlib.util.spec_from_file_location(f"bench_{kind}_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod        # a dataclass looks its module up
    spec.loader.exec_module(mod)
    return mod


@dataclass(frozen=True)
class Cell:
    name: str
    config: dict
    traffic: dict
    settings: dict
    chips: int
    end_to_end: tuple[dict, ...]
    per_layer: tuple[dict, ...]

    @classmethod
    def resolve(cls, name: str) -> "Cell":
        spec = _load(ROOT / "BENCHMARK.json")
        work = {w["name"]: w for w in spec["workloads"]}
        if name not in work:
            raise BenchError(f"no workload {name!r} in BENCHMARK.json")
        w = work[name]
        conf = {c["name"]: c for c in spec["configs"]}[w["config"]]

        def here(metrics):
            return tuple(m for m in metrics if name in m.get("workloads", [name]))

        cell = cls(name=name, config=_load(ROOT / conf["file"]),
                   traffic=_load(BENCH / "traffic" / f"{w['traffic']}.json"),
                   settings=_load(BENCH / "cells" / f"{name}.json"),
                   chips=int(w["chips"]), end_to_end=here(spec["end_to_end"]),
                   per_layer=here(spec["per_layer"]))
        cell.architecture       # a missing module is refused before JAX starts
        return cell

    @cached_property
    def architecture(self):
        """The configuration's architecture module,
        ``bench/models/<reference>.py``, loaded once: ``arch_config(config,
        positions)`` and ``counts(config)``."""
        return _module("models", self.config["reference"])

    def arch_config(self, positions: int):
        """The program's ``ArchConfig`` for requests of ``positions``
        tokens; the architecture refuses (``ValueError``) what the program
        would not run as the file states."""
        try:
            return self.architecture.arch_config(self.config, positions)
        except ValueError as e:
            raise BenchError(str(e)) from None


def peak_of(kind: str) -> dict:
    peaks = _load(BENCH / "peaks.json")
    if kind not in peaks:
        raise BenchError(f"no peaks for device kind {kind!r} in "
                         "bench/peaks.json")
    return peaks[kind]


class CompileLog:
    """Instants of the backend compilations after it was made."""

    def __init__(self):
        import jax
        self.at: list[float] = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, name, secs, **_):
        if name == "/jax/core/compile/backend_compile_duration":
            self.at.append(time.perf_counter())


def _block(results) -> None:
    import jax
    jax.block_until_ready(results)


class TraceWindow:
    """The profiler, opened at the first chunk dispatched ``after_s``
    after the first request's chunk, and closed once every request has
    retired.  The device is drained first, so the stretch holds all the
    device work of the chunks dispatched inside it and none of any
    other; closing after the window keeps the profiler's write out of
    the requests' way."""

    def __init__(self, log_dir: Path, after_s: float):
        self.log_dir, self.after_s = Path(log_dir), after_s
        self.opens_at: float | None = None
        self.mark_perf: float | None = None   # perf_counter at bench.clock
        self.active = self.done = False
        self.traced: list[tuple[int, int]] = []     # (rows, group devices)

    def arm(self, now: float) -> None:
        if self.opens_at is None:
            self.opens_at = now + self.after_s

    def before_chunk(self, rows: int, n_devices: int, pending) -> None:
        import jax
        if not self.active and not self.done and self.opens_at is not None \
                and time.perf_counter() >= self.opens_at:
            _block(pending)
            jax.profiler.start_trace(str(self.log_dir),
                                     profiler_options=trace_mod.profile_options())
            with jax.profiler.TraceAnnotation(trace_mod.MARK):
                self.mark_perf = time.perf_counter()
            self.active = True
        if self.active:
            self.traced.append((rows, n_devices))

    def close(self, pending) -> None:
        import jax
        if not self.active:
            return
        _block(pending)
        with jax.profiler.TraceAnnotation(trace_mod.STOP):
            pass
        jax.profiler.stop_trace()
        self.active, self.done = False, True


class Recorder:
    """The program's per-group step builder, wrapped.  It keeps the
    results of chunks that carry watched prompt rows (device arrays,
    copied to the host only after the window), annotates every chunk
    for the trace, and lets a test plant a fault where outputs are
    produced."""

    def __init__(self, builder, watch: dict, window: TraceWindow | None,
                 fault=None):
        self.builder, self.watch, self.window = builder, watch, window
        self.fault = fault
        self.kept: list[tuple[list, dict]] = []
        self.pending: dict[str, dict] = {}   # each group's latest result

    def __call__(self, group):
        import jax
        fn = self.builder(group)
        n_devices = len(group.devices)

        def run(chunk):
            tokens = chunk["tokens"]
            real = bool(np.any(tokens))     # warm-up rows are all zero
            if self.window is not None:
                if real:
                    self.window.arm(time.perf_counter())
                self.window.before_chunk(tokens.shape[0], n_devices,
                                         list(self.pending.values()))
            with jax.profiler.TraceAnnotation("bench.chunk"):
                res = fn(chunk)
            if self.fault is not None:
                res = self.fault(tokens, res)
            self.pending[group.name] = res
            hits = [(i, self.watch[row.tobytes()]) for i, row in
                    enumerate(np.asarray(tokens)) if row.tobytes() in self.watch]
            if hits and real:
                self.kept.append((hits, res))
            return res
        return run


@dataclass
class Run:
    """What one serving run leaves for the metric readers and the check.
    Times are ``time.perf_counter`` seconds."""

    cell: Cell
    seed: int
    n_requests: int
    records: list[dict]
    steps: list[dict]             # scheduler steps of the window
    setup_s: float
    warmup_s: float               # the program's warm-up steps, in setup_s
    compiles_in_window: int
    memory_peak_bytes: int
    counts: object                # the architecture's counts(config)
    peak: dict
    sample: dict | None           # prompts, served tokens, prefill logits
    trace: dict | None = None     # trace.compact(), or None
    trace_mark_perf: float | None = None   # perf_counter at its t0_ns
    traced: list = field(default_factory=list)   # (rows, devices) per chunk
    step_errors: list = field(default_factory=list)

    @property
    def completed(self) -> list[dict]:
        return [r for r in self.records if r["status"] == "completed"]


def _draw_sample(seed, n, records, rows_of, kept, need_rows):
    """Completed requests, drawn in the seed's order from the watched
    ones, until ``need_rows`` served rows are in hand."""
    where = {}
    for k, (hits, _) in enumerate(kept):
        for i, key in hits:
            where[key] = (k, i)
    done = {r["rid"] for r in records if r["status"] == "completed"}
    picked, rows = [], 0
    for rid in np.random.default_rng(seed).permutation(n):
        rid = int(rid)
        keys = [(rid, i) for i in range(rows_of[rid])]
        if rid in done and all(k in where for k in keys):
            picked += [(key, where[key]) for key in keys]
            rows += len(keys)
            if rows >= need_rows:
                break
    return picked


def serve(cell: Cell, *, seed: int, seconds: float, devices, t0: float,
          trace_dir: Path | None = None, fault=None) -> Run:
    """Serve the cell's traffic for ``seconds`` of arrivals; return the
    run with the program's state freed."""
    from repro.core.hetero import DeviceGroup
    from repro.launch.serve import (_memoize_per_group, _stream_step_builder,
                                    request_prompt, serve_requests,
                                    serving_model)
    from repro.serve import BatcherConfig

    tr, dep, st = cell.traffic, cell.config["deployment"], cell.settings
    if tr["arrival"] != "poisson":
        raise BenchError(f"arrival process {tr['arrival']!r}: serve_requests "
                         "offers Poisson arrivals only")
    p, g = tr["prompt_len"], tr["gen"]
    cfg = cell.arch_config(positions=p + g)
    rate = float(st["rate_rps"])
    n = traffic_mod.n_requests(rate, seconds)
    groups = [DeviceGroup(f"group{i}", [devices[j] for j in idx])
              for i, idx in enumerate(dep["groups"])]
    used = [d for grp in groups for d in grp.devices]

    # the requests the check may compare: the seed's first candidates,
    # watched by the prompt rows they carry
    most = max(tr["rows_choices"])
    watch = {}
    for rid in np.random.default_rng(seed).permutation(n)[
            :st["check"]["candidates"]]:
        for i, row in enumerate(request_prompt(cfg.vocab_size, tr["arrival_seed"],
                                               int(rid), most, p)):
            watch[row.tobytes()] = (int(rid), i)

    # the traced stretch: about the last ``last_s`` seconds of arrivals
    # and the drain after them
    window = TraceWindow(trace_dir, max(seconds - st["trace"]["last_s"], 0.0)) \
        if trace_dir else None
    rec = Recorder(_stream_step_builder(serving_model(cfg), prompt_len=p,
                                        gen=g, seed=seed), watch, window, fault)
    compiles = CompileLog()
    out = serve_requests(
        cfg, groups=groups, n_requests=n, rate_rps=rate, prompt_len=p, gen=g,
        seed=tr["arrival_seed"],
        batcher_config=BatcherConfig(
            max_batch_rows=dep["max_batch_rows"],
            coalesce_window_s=dep["coalesce_window_s"],
            queue_depth_rows=dep["queue_depth_rows"]),
        row_quantum=dep["row_quantum"], classes=traffic_mod.classes(tr),
        step_builder=_memoize_per_group(rec))
    if window is not None:
        window.close(list(rec.pending.values()))
    records = sorted(out["records"], key=lambda r: r["rid"])
    scheduler = out["scheduler"]
    scheduler.close()

    # the source opens as the program's warm-up steps end, before the
    # first arrival
    first = min(r["t_arrival"] for r in records)
    warmup = [h for h in scheduler.history
              if np.nanmax(h["row_done_at"]) < first]
    start = max((float(np.nanmax(h["row_done_at"])) for h in warmup),
                default=t0)
    off = traffic_mod.departures(tr, rate, n, records, start)
    if off:
        raise BenchError("the program's arrival source does not offer the "
                         "traffic mix: " + "; ".join(off))
    rows = {r["rid"]: r["rows"] for r in records}
    end = max((r["t_done"] for r in records if r["t_done"] is not None),
              default=start)
    steps = [h for h in scheduler.history
             if np.nanmin(h["row_done_at"]) > start]
    warmup_s = sum(h["t_step"] for h in warmup)
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in used)

    picked = _draw_sample(seed, n, records, rows, rec.kept,
                          st["check"]["rows"])
    sample = None
    if picked:
        host = {}
        for _, (k, _) in picked:
            if k not in host:
                res = rec.kept[k][1]
                host[k] = (np.asarray(res["tokens"]),
                           np.asarray(res["logits"], np.float32))
        prompts = {rid: request_prompt(cfg.vocab_size, tr["arrival_seed"],
                                       rid, rows[rid], p)
                   for rid in {key[0] for key, _ in picked}}
        sample = {
            "rows": [key for key, _ in picked],
            "prompts": np.stack([prompts[rid][i] for (rid, i), _ in picked]),
            "tokens": np.stack([host[k][0][i] for _, (k, i) in picked]),
            "logits": np.stack([host[k][1][i] for _, (k, i) in picked]),
        }

    run = Run(cell=cell, seed=seed, n_requests=n, records=records,
              steps=steps, setup_s=start - t0, warmup_s=warmup_s,
              compiles_in_window=sum(start <= t <= end for t in compiles.at),
              memory_peak_bytes=int(peak),
              counts=cell.architecture.counts(cell.config),
              peak=peak_of(used[0].device_kind) if used[0].platform == "tpu"
              else {}, sample=sample,
              traced=list(window.traced) if window else [],
              trace_mark_perf=window.mark_perf if window else None,
              step_errors=list(out["step_errors"]))
    # free the program's state before the reference runs on the device
    del out, scheduler, rec, window
    gc.collect()
    if trace_dir is not None:
        run.trace = trace_mod.compact(trace_mod.latest_xplane(trace_dir))
        shutil.rmtree(trace_dir)
    return run


def check(run: Run) -> dict:
    """Each number the check compares, with its limit, from the cell's
    reference over the sampled served rows."""
    limits = run.cell.settings["check"]["limits"]
    if run.sample is None:
        return {k: {"value": math.inf, "limit": v} for k, v in limits.items()}
    ref = _module("reference", run.cell.config["reference"])
    got = ref.readings(run.cell.config, run.seed, run.sample["prompts"],
                       run.sample["tokens"], run.sample["logits"],
                       block_rows=run.cell.settings["check"]["rows"])
    return {k: {"value": got[k], "limit": v} for k, v in limits.items()}


def correct(checks: dict) -> bool:
    return all(math.isfinite(c["value"]) and c["value"] <= c["limit"]
               for c in checks.values())


def measure(run: Run, metrics) -> dict:
    """Each metric the readers find something to read, with its unit."""
    out = {}
    for m in metrics:
        value = _module("metrics", m["name"]).read(run)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def result(run: Run, checks: dict, *, traced: bool, devices) -> dict:
    """The result line (its key order is the contract's; ``checks``
    comes last)."""
    d0 = devices[0]
    metrics = measure(run, run.cell.per_layer if traced
                      else run.cell.end_to_end)
    completed = len(run.completed)
    line = {"correct": correct(checks), "attempted": run.n_requests,
            "failed": run.n_requests - completed, "metrics": metrics,
            "device": {"platform": d0.platform, "kind": d0.device_kind,
                       "count": len(devices),
                       "memory_peak_bytes": run.memory_peak_bytes}}
    if traced and run.trace is not None:
        line["device"]["busy_s"] = trace_mod.busy_s(run.trace)
        line["device"]["window_s"] = trace_mod.window_s(run.trace)
        line["breakdown"] = {"device_ops": trace_mod.top_ops(run.trace),
                             "idle_gaps": trace_mod.idle_gaps(run.trace)}
    line["checks"] = checks
    return line


def run_cell(cell: Cell, *, seed: int, seconds: float, traced: bool,
             devices, t0: float, fault=None) -> dict:
    """Serve, check and measure one run; the result line as a dict."""
    trace_dir = ROOT / "results" / "trace" / cell.name if traced else None
    if trace_dir is not None and trace_dir.exists():
        shutil.rmtree(trace_dir)
    run = serve(cell, seed=seed, seconds=seconds, devices=devices, t0=t0,
                trace_dir=trace_dir, fault=fault)
    checks = check(run)
    out = result(run, checks, traced=traced, devices=devices)
    out["_run"] = run
    return out


def emit(line: dict) -> None:
    """The numbers compared, as the last lines of standard error, then
    the result as the last line of standard output."""
    out, err = sys.stdout, sys.stderr
    run = line.pop("_run", None)
    if run is not None:
        print(f"run: {len(run.completed)}/{run.n_requests} completed, "
              f"{len(run.steps)} steps, setup_s {run.setup_s:.3f} "
              f"(warm-up steps {run.warmup_s:.3f}), "
              f"compiles in window {run.compiles_in_window}, "
              f"step errors {len(run.step_errors)}", file=err)
    for name, c in line["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})", file=err)
    err.flush()
    print(json.dumps(line), file=out, flush=True)

