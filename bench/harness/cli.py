"""One run of one cell.

    --trace 0   set-up, then whole cycles for ``--seconds``; prints the
                cell's end-to-end metrics (``train_tokens_per_s``,
                ``setup_s``).
    --trace 1   set-up, then one whole cycle under the profiler; prints the
                cell's per-layer metrics, read from the trace.

Set-up (``setup_s``, from process start) loads the program, builds the
state from the seed, and drives it through one whole cycle, which
compiles the local step and the round, warms every shape the window
uses, and records what the check compares from the first steps. After the measurement the state is freed and the plain
reference replays the check steps; ``correct`` is their comparison
(``harness.check``). Each number compared is printed beside its limit,
last on standard error and last in the result line.
"""
from __future__ import annotations

import argparse
import json
import math
import shutil
import sys
import time
from dataclasses import dataclass

from bench.harness import check, device, spec
from bench.harness.spec import ROOT

CACHE_DIR = ROOT / ".bench_cache" / "jax"
TRACE_DIR = ROOT / ".bench_cache" / "trace"


def say(*parts):
    print("bench:", *parts, file=sys.stderr, flush=True)


@dataclass
class TraceContext:
    """What a per-layer reader gets: the reduced trace and the counts and
    yardstick numbers of the traced cycles."""
    trace: dict
    chips: int
    peaks: dict
    steps: int
    rounds: int
    step_flops: float
    round_bytes: int


class CompileCount:
    """Programs lowered and compiled while ``on``."""

    def __init__(self):
        import jax

        self.on, self.lowered, self.compiled = False, 0, 0
        jax.monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, event, duration, **_):
        if not self.on:
            return
        if event == "/jax/core/compile/jaxpr_to_mlir_module_duration":
            self.lowered += 1
        elif event == "/jax/core/compile/backend_compile_duration":
            self.compiled += 1


def use_compile_cache():
    """JAX's persistent compilation cache, at a fixed path inside the
    checkout, for every program however fast it compiles."""
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def _free(tree):
    import jax

    for leaf in jax.tree.leaves(tree):
        leaf.delete()


def parse(argv):
    ap = argparse.ArgumentParser(prog="bench/run.py")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv, t0: float, cell=None, devices=None, peaks=None) -> int:
    """A run. ``cell``, ``devices`` and ``peaks`` stand in for the
    ``BENCHMARK.json`` entry and the chip in tests on the CPU."""
    args = parse(argv)
    cell = cell or spec.resolve(args.workload)
    import jax

    if devices is None:
        try:
            devices = device.require_chips(cell.chips)
            peaks = device.peaks_for(devices[0].device_kind)
        except device.NoChip as e:
            say(f"refused: {e}")
            return 2
        use_compile_cache()

    from bench.harness import reference
    from bench.harness.train import TrainCell

    compiles = CompileCount()
    tc = TrainCell(cell.config, cell.traffic, devices)
    state, prog = tc.first_cycle(args.seed)
    jax.block_until_ready(state)
    setup_s = time.time() - t0
    say(f"setup_s {setup_s:.3f}; check losses {prog['loss']}")

    compiles.on = True
    if args.trace:
        metrics, run, extra = _traced(tc, state, cell, peaks)
    else:
        state, run = tc.cycles(state, args.seconds)
        tokens = run["steps"] * tc.tokens_per_step()
        metrics = {"train_tokens_per_s": {"value": tokens / run["wall_s"],
                                          "unit": "tokens/s"},
                   "setup_s": {"value": setup_s, "unit": "s"}}
        extra = {}
    compiles.on = False
    say(f"window: {run['cycles']} cycles, {run['steps']} local steps, "
        f"{run['rounds']} rounds in {run['wall_s']:.4f} s; compiles in the "
        f"window: {compiles.lowered} lowered, {compiles.compiled} compiled")
    dev = dict(device.describe(devices),
               memory_peak_bytes=device.memory_peak_bytes(devices), **extra)
    failed = sum(1 for x in run["losses"] if not math.isfinite(x))
    if "state" in run:
        state = run.pop("state")
    _free(state)
    del state

    ref = reference.Reference(cell.config, cell.traffic).run(args.seed, devices)
    numbers = check.gaps(prog, ref)
    correct, rows = check.judge(numbers, cell.limits)
    correct = correct and failed == 0
    say(f"reference losses {ref['loss']}; leaves left out: "
        f"{numbers['leaves_left_out']}")
    for n in check.NAMES:
        if n not in cell.limits:
            say(f"not compared: {n} {numbers[n]!r}")
    for r in rows:
        say(f"check {r['name']} {r['value']!r} limit {r['limit']!r}")
    result = {"correct": correct, "attempted": run["steps"] + run["rounds"],
              "failed": failed, "metrics": metrics, "device": dev}
    if "breakdown" in extra:
        result["breakdown"] = dev.pop("breakdown")
    result["checks"] = {r["name"]: {"value": r["value"], "limit": r["limit"]}
                        for r in rows}
    print(json.dumps(result), flush=True)
    return 0


def _traced(tc, state, cell, peaks):
    """One whole cycle under the profiler; the per-layer metrics."""
    import jax
    from jax.profiler import TraceAnnotation

    from bench.harness import trace, yardstick

    shutil.rmtree(TRACE_DIR, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = False
    jax.profiler.start_trace(str(TRACE_DIR), profiler_options=opts)
    t = time.perf_counter()
    with TraceAnnotation(trace.WINDOW):
        ds = tc.driver.run(state, tc.feed())
        jax.block_until_ready(ds.state)
    wall = time.perf_counter() - t
    jax.profiler.stop_trace()
    red = trace.reduce(trace.load(trace.find_xplane(str(TRACE_DIR))))
    run = {"cycles": 1, "wall_s": wall, "steps": ds.iters_total,
           "rounds": ds.rounds_total, "state": ds.state,
           "losses": [x for r in ds.results for x in r.losses]}
    replicas = cell.traffic["clients"] // cell.chips
    ctx = TraceContext(trace=red, chips=cell.chips, peaks=peaks,
                       steps=run["steps"], rounds=run["rounds"],
                       step_flops=yardstick.step_flops(cell.config, cell.traffic),
                       round_bytes=yardstick.round_least_bytes(
                           tc.state_shapes, replicas))
    metrics = {}
    for m in cell.per_layer:
        value = spec.metric_reader(m["name"]).read(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    say(f"trace: window {red['window_s']:.6f} s, busy {red['busy_s']:.6f} s; "
        f"programs {[d['programs'] for d in red['devices']]}")
    extra = {"busy_s": red["busy_s"], "window_s": red["window_s"],
             "breakdown": {"device_ops": red["device_ops"],
                           "idle_gaps": red["idle_gaps"]}}
    return metrics, run, extra
