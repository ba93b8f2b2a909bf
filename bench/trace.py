"""From a JAX profiler trace to the few events the metrics read.

A traced run opens the profiler at a chunk boundary inside the window and
closes it at a later one (``harness.TraceWindow``).  ``compact`` reads the
``.xplane.pb`` it wrote and keeps, for each TPU, the program executions
(``XLA Modules``) and the operations (``XLA Ops``), and from the host the
events that last long enough to explain an idle gap, with the harness's
own ``bench.*`` annotations.  The metric readers and the tests work on
that compact form, which is plain JSON.

Which program is which: the serving step builder jits the decode step as
``model.decode_step``, so its module is ``jit_decode_step``; it jits the
prefill as a lambda, so its module is ``jit__lambda``.  Both rules were
read off a trace of the chip by hand; a program that names its steps
(``jax.named_call`` or a named function) needs them changed here.
"""

from __future__ import annotations

import re
from pathlib import Path

PREFILL = re.compile(r"^jit__lambda\b")
DECODE = re.compile(r"^jit_decode_step\b")

HOST_MIN_NS = 20_000          # host events shorter than this explain no gap
GAP_MIN_NS = 50_000           # idle gaps shorter than this are not listed
MARK, STOP = "bench.clock", "bench.stop"


def profile_options():
    """Host spans and device activity; no Python function tracer, which
    would slow the host loop it is meant to observe."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    return opts


def latest_xplane(log_dir: Path) -> Path:
    found = sorted(Path(log_dir).glob("plugins/profile/*/*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def _module_name(name: str) -> str:
    # "jit_decode_step(12)" -> "jit_decode_step"
    return name.split("(", 1)[0].strip()


CONTROL_FLOW = re.compile(r"^(while|conditional|call)$")


def op_label(text: str) -> str:
    """``%fusion.154 bf16[32,11008] fusion`` from an XLA op's HLO text."""
    name, _, rest = text.partition(" = ")
    kind = re.search(r"\}?\s*([a-z][\w-]*)\(", rest)
    shape = rest.split("{", 1)[0].strip()[:60]
    return f"{name} {shape} {kind.group(1) if kind else ''}".strip()


def compact(xplane: Path) -> dict:
    """The device programs (by module name) and ops (by ``op_label``),
    and the host events, of the stretch between the harness's
    ``bench.clock`` and ``bench.stop`` marks."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(str(xplane))
    devices, host = [], []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:") and \
                plane.name[len("/device:TPU:"):].isdigit():
            dev = {"name": plane.name, "modules": [], "ops": []}
            for line in plane.lines:
                key = {"XLA Modules": "modules", "XLA Ops": "ops"}.get(line.name)
                if key is None:
                    continue
                label = _module_name if key == "modules" else op_label
                dev[key] = [[label(e.name), int(e.start_ns), int(e.duration_ns)]
                            for e in line.events]
            devices.append(dev)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.duration_ns >= HOST_MIN_NS or e.name.startswith("bench."):
                        host.append([e.name, int(e.start_ns), int(e.duration_ns)])
    devices.sort(key=lambda d: int(d["name"].rsplit(":", 1)[1]))
    marks = {name: [e for e in host if e[0] == name] for name in (MARK, STOP)}
    if not marks[MARK] or not marks[STOP]:
        raise ValueError("trace lacks the harness's bench.clock/bench.stop marks")
    # The device was drained before the profiler opened and before it
    # closed, so every device event belongs to the stretch.  The device's
    # clock sits some 0.1 ms off the host's, so the stretch is the hull
    # of the host marks and the device events.
    t0 = marks[MARK][0][1]
    t1 = marks[STOP][-1][1] + marks[STOP][-1][2]
    for d in devices:
        for e in d["modules"] + d["ops"]:
            t0, t1 = min(t0, e[1]), max(t1, e[1] + e[2])
    return {"t0_ns": t0, "t1_ns": t1, "devices": devices,
            "host": [e for e in host if t0 <= e[1] <= t1]}


# -- reductions on the compact form ------------------------------------------

def union(intervals) -> list[tuple[int, int]]:
    """Merged, sorted ``(start, end)`` intervals."""
    out: list[list[int]] = []
    for s, e in sorted((s, s + d) for s, d in intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def busy(device: dict) -> list[tuple[int, int]]:
    """Intervals in which an operation ran on this device."""
    events = device["ops"] or device["modules"]
    return union((e[1], e[2]) for e in events)


def overlap(intervals, lo: int, hi: int) -> int:
    return sum(max(0, min(e, hi) - max(s, lo)) for s, e in intervals)


def window_s(tr: dict) -> float:
    return (tr["t1_ns"] - tr["t0_ns"]) / 1e9


def busy_s(tr: dict) -> float:
    """Seconds with an operation running, averaged over the devices."""
    per = [overlap(busy(d), tr["t0_ns"], tr["t1_ns"]) for d in tr["devices"]]
    return sum(per) / len(per) / 1e9 if per else 0.0


def program(tr: dict, pattern: re.Pattern) -> tuple[float, int]:
    """(device seconds, executions), both summed over every device, of
    the programs whose module name matches ``pattern``."""
    hits = [e for d in tr["devices"] for e in d["modules"]
            if pattern.search(e[0])]
    return sum(e[2] for e in hits) / 1e9, len(hits)


def top_ops(tr: dict, n: int = 10) -> list[list]:
    """The device operations that took the most time, in seconds per
    device.  Loops and calls, which contain other operations, are not
    counted themselves."""
    total: dict[str, int] = {}
    for d in tr["devices"]:
        for label, _, dur in d["ops"]:
            if CONTROL_FLOW.match(label.rsplit(" ", 1)[-1]):
                continue
            total[label] = total.get(label, 0) + dur
    k = max(len(tr["devices"]), 1)
    ranked = sorted(total.items(), key=lambda kv: -kv[1])[:n]
    return [[name, ns / k / 1e9] for name, ns in ranked]


def idle_gaps(tr: dict, n: int = 10) -> list[list]:
    """The longest idle gaps of the first device inside the traced window,
    each named by the innermost host event that spans its middle."""
    if not tr["devices"]:
        return []
    edges = [tr["t0_ns"]]
    for s, e in busy(tr["devices"][0]):
        edges += [s, e]
    edges.append(tr["t1_ns"])
    gaps = [(a, b) for a, b in zip(edges[::2], edges[1::2])
            if b - a >= GAP_MIN_NS]
    gaps.sort(key=lambda g: g[0] - g[1])
    out = []
    for a, b in gaps[:n]:
        mid = (a + b) // 2
        spans = [h for h in tr["host"] if h[1] <= mid <= h[1] + h[2]
                 and h[0] not in (MARK, STOP)]
        name = min(spans, key=lambda h: h[2])[0] if spans else "no host event"
        out.append([name, (b - a) / 1e9])
    return out

