"""Reduce a profiler trace (``.xplane.pb``) to the benchmark's numbers.

On a TPU the profiler writes one plane per chip (``/device:TPU:<n>``)
with a line ``XLA Modules`` (one event per program execution, named
``jit_<function>(<id>)``) and a line ``XLA Ops`` (one event per HLO
operation; a ``while`` spans the operations of its body), and a host
plane ``/host:CPU`` whose thread lines carry the benchmark's
``jax.profiler.TraceAnnotation`` spans. Host and device events share one
clock, in nanoseconds from the start of the trace.

The traced window is the host span named ``WINDOW``. Within it, per
device:

- busy: the union of the ``XLA Ops`` intervals; idle is the rest;
- each program's device time and call count, from ``XLA Modules``;
- collective time: ``XLA Ops`` events that are all-reduce, all-gather,
  reduce-scatter, collective-permute or all-to-all (with their -start and
  -done halves);
- each operation's total time (container operations left out);
- the idle gaps, each named by the innermost benchmark host span open at
  its midpoint (``driver`` where none is: the driver's own host code).
"""
from __future__ import annotations

import glob
import os
import re
from collections import defaultdict

WINDOW = "bench.traced"
HOST_PREFIX = "bench."
_KIND = re.compile(r"= .*? ([a-z][\w\-]*)\(")
_COLLECTIVE = re.compile(
    r"^(all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all)")
_CONTAINERS = {"while", "conditional", "call"}


def find_xplane(logdir: str) -> str:
    files = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"), recursive=True)
    if len(files) != 1:
        raise FileNotFoundError(f"expected one .xplane.pb under {logdir}, "
                                f"found {len(files)}")
    return files[0]


def op_kind(name: str) -> str:
    m = _KIND.search(name)
    return m.group(1) if m else name.split(" ", 1)[0]


def op_name(name: str) -> str:
    return name.split(" = ", 1)[0].lstrip("%")


def program_name(module_event_name: str) -> str:
    base = module_event_name.split("(", 1)[0]
    return base[4:] if base.startswith("jit_") else base


def union_length(intervals) -> float:
    """Total length covered by (start, end) intervals."""
    total, end = 0.0, None
    for s, e in sorted(intervals):
        if end is None or s > end:
            total += e - s
            end = e
        elif e > end:
            total += e - end
            end = e
    return total


def idle_intervals(intervals, t0: float, t1: float):
    """The parts of [t0, t1] that no interval covers."""
    gaps, cur = [], t0
    for s, e in sorted(intervals):
        if s > cur:
            gaps.append((cur, min(s, t1)))
        cur = max(cur, e)
        if cur >= t1:
            break
    if cur < t1:
        gaps.append((cur, t1))
    return [(s, e) for s, e in gaps if e > s]


def _clip(s, e, t0, t1):
    return max(s, t0), min(e, t1)


def host_spans(pd):
    spans = []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(HOST_PREFIX):
                    spans.append((ev.name, ev.start_ns,
                                  ev.start_ns + ev.duration_ns))
    return spans


def label_at(spans, t: float) -> str:
    """The innermost benchmark host span open at ``t`` (the window's own
    span aside)."""
    best = None
    for name, s, e in spans:
        if name != WINDOW and s <= t <= e and (best is None or e - s < best[1]):
            best = (name, e - s)
    return best[0] if best else "driver"


def reduce(pd, top: int = 10) -> dict:
    """The trace's numbers, times in seconds."""
    spans = host_spans(pd)
    windows = [(s, e) for n, s, e in spans if n == WINDOW]
    if len(windows) != 1:
        raise ValueError(f"expected one {WINDOW!r} host span, found "
                         f"{len(windows)}")
    t0, t1 = windows[0]
    devices = sorted((p for p in pd.planes if p.name.startswith("/device:TPU:")),
                     key=lambda p: int(p.name.rsplit(":", 1)[1]))
    if not devices:
        raise ValueError("the trace holds no TPU device plane")
    per_device, op_time, all_gaps = [], defaultdict(float), []
    for plane in devices:
        lines = {line.name: line for line in plane.lines}
        ops, coll = [], 0.0
        for ev in (lines["XLA Ops"].events if "XLA Ops" in lines else ()):
            s, e = _clip(ev.start_ns, ev.start_ns + ev.duration_ns, t0, t1)
            if e <= s:
                continue
            ops.append((s, e))
            kind = op_kind(ev.name)
            if _COLLECTIVE.match(kind):
                coll += e - s
            if kind not in _CONTAINERS:
                op_time[op_name(ev.name)] += (e - s) / len(devices)
        programs = defaultdict(lambda: {"calls": 0, "device_s": 0.0})
        for ev in (lines["XLA Modules"].events if "XLA Modules" in lines else ()):
            s, e = ev.start_ns, ev.start_ns + ev.duration_ns
            if s >= t0 and e <= t1:
                prog = programs[program_name(ev.name)]
                prog["calls"] += 1
                prog["device_s"] += (e - s) * 1e-9
        all_gaps += idle_intervals(ops, t0, t1)
        per_device.append({"name": plane.name,
                           "busy_s": union_length(ops) * 1e-9,
                           "collective_s": coll * 1e-9,
                           "programs": dict(programs)})
    window_s = (t1 - t0) * 1e-9
    busy_s = sum(d["busy_s"] for d in per_device) / len(per_device)
    top_ops = sorted(op_time.items(), key=lambda kv: -kv[1])[:top]
    longest = sorted(all_gaps, key=lambda g: g[0] - g[1])[:top]
    return {"window_s": window_s, "busy_s": busy_s, "devices": per_device,
            "device_ops": [[n, t * 1e-9] for n, t in top_ops],
            "idle_gaps": [[label_at(spans, (s + e) / 2), (e - s) * 1e-9]
                          for s, e in longest]}


def load(path: str):
    from jax.profiler import ProfileData

    return ProfileData.from_file(path)
