"""Measured-time profiling — closing the loop on the modeled clocks.

Everything else in ``repro.obs`` prices runs on *modeled* clocks (the
α–β ledger, the roofline serve steps). This module measures the same
jitted steps on the host and reports the skew:

  * ``ProfileSession`` — a context manager that (optionally) wraps the
    run in a ``jax.profiler`` trace session (``logdir=`` writes the
    XPlane/TensorBoard artifact; a profiler that cannot start or stop
    raises) and records per-call
    block-until-ready wall timings next to their modeled prices;
  * ``skew_table()`` — per-step-name rows ``{name, calls, modeled_s,
    measured_s, skew}`` where ``skew = measured / modeled`` (>1: the
    model is optimistic; <1: the host beat the roofline — e.g. smoke
    shapes fitting in cache);
  * ``emit_spans()`` — one ``profile.<name>`` span per measured call on
    the **wall** clock carrying both ``modeled_s`` and ``measured_s``
    attrs. Wall spans are excluded from the determinism fingerprints by
    construction (``Span.key()``), so measured time still never leaks
    into the modeled/virtual ledgers.

Surfaced by ``launch/train.py --profile`` (jitted train/sync steps
against the DeviceModel roofline and the topology's α–β round price) and
``launch/serve.py --profile`` (prefill/decode steps against the serve
roofline).
"""
from __future__ import annotations

import functools
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Union

import jax

from repro.obs.trace import CAT_COMPUTE, WALL

__all__ = ["ProfileSession", "StepTiming", "format_skew_table"]


@dataclass
class StepTiming:
    """One measured call of one profiled step."""

    name: str
    modeled_s: float           # the clock-domain price of this call
    measured_s: float          # block-until-ready host seconds
    t0: float                  # time.monotonic() at call start
    t1: float
    attrs: Dict[str, Any] = field(default_factory=dict)

    @property
    def skew(self) -> float:
        return (self.measured_s / self.modeled_s if self.modeled_s > 0
                else float("inf"))


class ProfileSession:
    """Collects modeled-vs-measured step timings for one run.

    Use as a context manager; with ``logdir`` set the session brackets
    the run in ``jax.profiler.start_trace``/``stop_trace`` (XPlane +
    trace.json.gz under ``logdir`` — TensorBoard/XProf-loadable). The
    wall-timing harness works regardless: ``step`` / ``wrap`` time each
    call with ``block_until_ready`` so async dispatch can't hide device
    time.
    """

    def __init__(self, logdir: Optional[str] = None):
        self.logdir = logdir
        self.records: List[StepTiming] = []
        self._tracing = False

    # -- jax.profiler session -----------------------------------------------

    def __enter__(self) -> "ProfileSession":
        if self.logdir:
            jax.profiler.start_trace(self.logdir)
            self._tracing = True
        return self

    def __exit__(self, *exc):
        if self._tracing:
            self._tracing = False
            jax.profiler.stop_trace()
        return False

    # -- the wall-timing harness --------------------------------------------

    def measure(self, fn: Callable, *args, **kwargs):
        """Call ``fn`` and block until its outputs are ready; returns
        ``(out, t0, t1)`` on ``time.monotonic()``."""
        t0 = time.monotonic()
        out = jax.block_until_ready(fn(*args, **kwargs))
        return out, t0, time.monotonic()

    def record(self, name: str, modeled_s: float, measured_s: float,
               t0: float = 0.0, t1: float = 0.0, **attrs):
        self.records.append(StepTiming(name=name, modeled_s=float(modeled_s),
                                       measured_s=float(measured_s),
                                       t0=t0, t1=t1, attrs=attrs))

    def step(self, name: str, modeled_s: float, fn: Callable,
             *args, **kwargs):
        """Measure one call of ``fn`` against its modeled price."""
        out, t0, t1 = self.measure(fn, *args, **kwargs)
        self.record(name, modeled_s, t1 - t0, t0, t1)
        return out

    def wrap(self, fn: Callable, name: str,
             modeled_s: Union[float, Callable[..., float]]) -> Callable:
        """A call-compatible wrapper of ``fn`` that records every call.

        ``modeled_s`` is a constant price or a ``(*args, **kwargs) ->
        seconds`` callable evaluated per call. ``functools.wraps``
        preserves ``__wrapped__``, so ``local_sgd.sync_step_topology``
        still finds the round's topology through the wrapper.
        """

        @functools.wraps(fn)
        def wrapped(*args, **kwargs):
            price = (modeled_s(*args, **kwargs) if callable(modeled_s)
                     else modeled_s)
            return self.step(name, price, fn, *args, **kwargs)

        return wrapped

    # -- reporting ----------------------------------------------------------

    def skew_table(self) -> List[dict]:
        """Per-name totals: every profiled span carries both modeled and
        measured seconds; ``skew = measured / modeled``."""
        by: Dict[str, dict] = {}
        for r in self.records:
            row = by.setdefault(r.name, {"name": r.name, "calls": 0,
                                         "modeled_s": 0.0, "measured_s": 0.0})
            row["calls"] += 1
            row["modeled_s"] += r.modeled_s
            row["measured_s"] += r.measured_s
        out = []
        for name in sorted(by):
            row = by[name]
            row["skew"] = (row["measured_s"] / row["modeled_s"]
                           if row["modeled_s"] > 0 else float("inf"))
            out.append(row)
        return out

    def emit_spans(self, tracer, track: str = "profiler"):
        """Wall-clock ``profile.<name>`` spans, one per measured call,
        attrs carrying both timelines (``modeled_s`` / ``measured_s`` /
        ``skew``). Kept off the virtual/modeled clocks so measured time
        never enters the deterministic fingerprints."""
        if not tracer:
            return
        for r in self.records:
            tracer.add(f"profile.{r.name}", r.t0, r.t1, cat=CAT_COMPUTE,
                       track=track, clock=WALL,
                       attrs=dict(r.attrs, modeled_s=r.modeled_s,
                                  measured_s=r.measured_s, skew=r.skew))


def format_skew_table(rows: List[dict]) -> str:
    """Render ``skew_table()`` rows as an aligned text table."""
    if not rows:
        return "(no profiled steps)"
    lines = [f"{'step':<16} {'calls':>6} {'modeled_s':>12} "
             f"{'measured_s':>12} {'skew':>8}"]
    for r in rows:
        lines.append(f"{r['name']:<16} {r['calls']:>6d} "
                     f"{r['modeled_s']:>12.4e} {r['measured_s']:>12.4e} "
                     f"{r['skew']:>8.2f}")
    return "\n".join(lines)
