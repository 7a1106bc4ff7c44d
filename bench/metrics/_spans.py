"""Shared by the readers of the training loop's own spans: the device's
idle time put down to what the host loop was doing.

The program writes a profiler annotation ``stl.<name>`` for each wall span
of its training loop (``repro.obs.trace``): ``stl.step`` holds
``stl.input``, ``stl.dispatch``, ``stl.wait`` and ``stl.loss_read``;
``stl.reduce`` holds a ``stl.dispatch`` and a ``stl.wait``;
``stl.stage_end`` a ``stl.loss_read``; ``stl.local_steps``, ``stl.stage``
and ``stl.run`` enclose them. They lie on the driver's thread (the host
line holding the window's span), on the clock of the device's ``XLA Ops``.

Within the traced window, each interval in which a device runs no
operation is cut exactly at every ``stl.*`` span boundary, and each piece
goes to the innermost ``stl.*`` span open over it (``None`` where none
is). A trace in which the program wrote no such span reads as nothing.
"""
from __future__ import annotations

import os
from collections import Counter, defaultdict
from functools import lru_cache

from bench.harness import cli, trace

PREFIX = "stl."


def segments(spans, t0, t1):
    """[t0, t1] cut at every boundary of ``spans`` (``(name, start, end)``
    on one thread, nested): ``(start, end, name)`` pieces in time order,
    ``name`` that of the innermost span open over the piece, or ``None``."""
    bounds = []
    for i, (_, s, e) in enumerate(spans):
        s, e = max(s, t0), min(e, t1)
        if e > s:
            # at one instant: ends first, then starts, outer before inner
            bounds += [(s, 1, s - e, i), (e, 0, 0, i)]
    bounds.sort()
    pieces, open_, cur = [], [], t0
    for t, starts, _, i in bounds:
        if t > cur:
            pieces.append((cur, t, spans[open_[-1]][0] if open_ else None))
            cur = t
        if starts:
            open_.append(i)
        else:
            open_.remove(i)
    if cur < t1:
        pieces.append((cur, t1, None))
    return pieces


def split_idle(gaps, pieces) -> dict:
    """Length of the sorted, disjoint ``gaps`` under each piece's name."""
    out, i = defaultdict(float), 0
    for gs, ge in gaps:
        while i < len(pieces) and pieces[i][1] <= gs:
            i += 1
        j = i
        while j < len(pieces) and pieces[j][0] < ge:
            a, b, name = pieces[j]
            out[name] += min(b, ge) - max(a, gs)
            j += 1
    return dict(out)


def _driver_line(pd):
    """The window's ``(start, end)`` and the host line it is on."""
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name == trace.WINDOW:
                    return (ev.start_ns, ev.start_ns + ev.duration_ns), line
    raise ValueError(f"the trace holds no {trace.WINDOW!r} host span")


def summarise(pd):
    """The window's ``stl.*`` span counts and, per TPU device, its idle
    time (ns) and that time split by innermost span; ``None`` where the
    program wrote no ``stl.*`` span."""
    (t0, t1), line = _driver_line(pd)
    spans = [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
             for ev in line.events if ev.name.startswith(PREFIX)]
    spans = [sp for sp in spans if sp[2] > t0 and sp[1] < t1]
    if not spans:
        return None
    pieces = segments(spans, t0, t1)
    devices = []
    for plane in pd.planes:
        if not plane.name.startswith("/device:TPU:"):
            continue
        ops = []
        for ln in plane.lines:
            if ln.name != "XLA Ops":
                continue
            for ev in ln.events:
                s = max(ev.start_ns, t0)
                e = min(ev.start_ns + ev.duration_ns, t1)
                if e > s:
                    ops.append((s, e))
        gaps = trace.idle_intervals(ops, t0, t1)
        devices.append({"idle_ns": sum(e - s for s, e in gaps),
                        "by_span": split_idle(gaps, pieces)})
    return {"counts": Counter(n for n, s, _ in spans if t0 <= s <= t1),
            "devices": devices}


@lru_cache(maxsize=4)
def _summary(path: str, mtime: float):
    return summarise(trace.load(path))


def cycle():
    """``summarise`` of the traced cycle's trace, read once per file."""
    try:
        path = trace.find_xplane(str(cli.TRACE_DIR))
    except FileNotFoundError:
        return None
    return _summary(path, os.path.getmtime(path))


def idle_ms_per(names, count):
    """Device-idle ms under the spans ``names`` over ``count``, mean over
    the chips."""
    c = cycle()
    if not c or not c["devices"] or not count:
        return None
    per_chip = [sum(d["by_span"].get(PREFIX + n, 0.0) for n in names)
                for d in c["devices"]]
    return 1e-6 * sum(per_chip) / len(per_chip) / count
