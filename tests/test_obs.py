"""repro.obs: span tracing, metrics registry, export, bench diffing.

The decisive invariants:
  * disabled tracing is free and silent: ``NULL_TRACER`` is falsy, its
    context manager is shared/no-op, and an untraced run records nothing;
  * determinism: same (config, seed) ⇒ identical span trees — including
    virtual/modeled timestamps — across repeated EventBackend runs, for
    the synchronous, streaming-upload and asynchronous regimes;
  * the trace *is* the ledger: on the modeled α–β timeline, each
    ``reduce[hop]`` span's bytes equal the bit-exact sum of its
    ``reduce_leaf`` children and its seconds their float-sum, for dense
    and int8 reducers on star, streaming and hierarchical topologies;
    on the virtual clock, streaming ``reduce_leaf`` spans sum to the
    run's ``leaf_ledger``;
  * metrics are one process-local registry: counters/gauges/histograms
    with labels, kind-checked registration, serializable snapshots that
    ``Engine.run`` copies into ``EngineReport.metrics``;
  * the Chrome-trace export is Perfetto-loadable: one process per clock
    domain, named thread rows, µs timestamps, attrs under ``args``;
  * BENCH_*.json diffing gates regressions: schema violations raise,
    a >tol increase in a monitored column regresses, scale-mismatched
    artifacts are skipped, and ``tools/bench_diff.py`` exits 0/1/2.
"""
import io
import json
import math
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from repro import runtime
from repro.configs.base import TrainConfig
from repro.core import simulate
from repro.core.local_sgd import build_sync_step, sync_step_topology
from repro.engine import Hierarchical
from repro.data import make_binary_classification, partition_iid
from repro.models import logreg
from repro.obs import (
    MODELED,
    NULL_TRACER,
    VIRTUAL,
    WALL,
    BenchSchemaError,
    Tracer,
    diff_benches,
    diff_dirs,
    to_chrome_trace,
    validate_bench,
    write_chrome_trace,
    write_jsonl,
)
from repro.obs import metrics as obs_metrics
from repro.utils.logging import StructuredLogger

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _fresh_registry():
    obs_metrics.reset()
    yield
    obs_metrics.reset()


@pytest.fixture(scope="module")
def problem():
    x, y = make_binary_classification(n=256, d=16, seed=0)
    lam = 1e-3
    data = {k: jnp.asarray(v)
            for k, v in partition_iid(x, y, 4, seed=1).items()}
    xj, yj = jnp.asarray(x), jnp.asarray(y)
    loss_fn = lambda p, b: logreg.loss_fn(p, b, lam)
    eval_fn = jax.jit(lambda p: logreg.full_objective(p, xj, yj, lam))
    return loss_fn, eval_fn, logreg.init_params(None, 16), data


def _cfg(**kw):
    base = dict(algo="stl_sc", eta1=0.5, T1=16, k1=2.0, n_stages=2,
                batch_per_client=16, seed=0, base_step_time_s=1e-3)
    base.update(kw)
    return TrainConfig(**base)


# ---------------------------------------------------------------------------
# Tracer primitives
# ---------------------------------------------------------------------------

def test_null_tracer_is_falsy_and_noop():
    assert not NULL_TRACER
    assert NULL_TRACER.spans == []
    with NULL_TRACER.span("stage", attrs={"s": 1}) as sp:
        sp.set(rounds=3)                      # must be accepted and ignored
    assert NULL_TRACER.add("reduce", 0.0, 1.0) is None
    assert NULL_TRACER.begin("round", 0.0) is None
    assert NULL_TRACER.spans == []


def test_null_tracer_span_is_a_profiler_annotation_only():
    from jax.profiler import TraceAnnotation

    sp = NULL_TRACER.span("step", attrs={"s": 1, "tree": [1]}, step_num=3)
    assert isinstance(sp, TraceAnnotation)
    with sp:
        sp.set(rounds=2, skipped=None)
    with NULL_TRACER.span("stage", attrs={"s": 1}) as sp:
        sp.set(rounds=3)
    assert NULL_TRACER.spans == []


def test_wall_spans_reach_the_profiler_and_others_do_not(tmp_path):
    """Wall spans of a Tracer and of NULL_TRACER become ``stl.*``
    annotations on the profiler's clock, with their scalar attributes;
    spans on the virtual and modeled clocks never do."""
    import glob

    from jax.profiler import ProfileData

    tr = Tracer()
    jax.profiler.start_trace(str(tmp_path))
    with tr.span("stage", attrs={"s": 2, "tree": [1]}) as sp:
        with NULL_TRACER.span("step", step_num=7):
            tr.add("reduce", 0.0, 1.0, clock=MODELED)
            tr.instant("broadcast", 1.0)
        sp.set(rounds=3)
    jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    events = {ev.name: dict(ev.stats)
              for plane in ProfileData.from_file(path).planes
              if plane.name.startswith("/host:")
              for line in plane.lines for ev in line.events
              if ev.name.startswith("stl.")}
    assert set(events) == {"stl.stage", "stl.step"}
    assert events["stl.stage"] == {"s": 2, "rounds": 3}
    assert events["stl.step"]["step_num"] == 7
    assert [s.name for s in tr.spans] == ["stage", "reduce", "broadcast"]
    assert NULL_TRACER.spans == []


def test_untraced_run_records_nothing(problem):
    loss_fn, eval_fn, p0, data = problem
    before = len(NULL_TRACER.spans)
    simulate.run(loss_fn, p0, data, _cfg(), eval_fn, eval_every=8)
    assert len(NULL_TRACER.spans) == before == 0


def test_tracer_nesting_and_views():
    tr = Tracer(run_id="t")
    rid = tr.begin("round", 0.0, clock=VIRTUAL, attrs={"k": 2})
    tr.add("local_steps", 0.0, 1.0, clock=VIRTUAL, track="client/0")
    tr.instant("broadcast", 2.0, clock=VIRTUAL)
    tr.end(rid, 2.0)
    with tr.span("stage", attrs={"s": 1}) as sp:
        sp.set(rounds=1)
    round_span = tr.find("round")[0]
    kids = list(tr.children(round_span))
    assert [s.name for s in kids] == ["local_steps", "broadcast"]
    assert all(s.parent == round_span.id for s in kids)
    assert round_span.parent == -1
    assert tr.find("broadcast")[0].duration == 0.0
    stage = tr.find("stage", clock=WALL)[0]
    assert stage.attrs == {"s": 1, "rounds": 1}
    # wall timestamps are excluded from the structural key, virtual kept
    assert stage.key()[6:8] == (None, None)
    assert round_span.key()[6:8] == (0.0, 2.0)


# ---------------------------------------------------------------------------
# Determinism: same seed ⇒ identical span tree
# ---------------------------------------------------------------------------

def _traced_run(problem, cfg):
    loss_fn, eval_fn, p0, data = problem
    tr = Tracer()
    runtime.run(loss_fn, p0, data, cfg, eval_fn, eval_every=8, tracer=tr)
    return tr


@pytest.mark.parametrize("kw", [
    dict(),                                                    # homogeneous
    dict(straggler_frac=0.25, straggler_slowdown=2.0,
         dropout_rate=0.25, upload_schedule="streaming"),      # event-rich
    dict(async_mode=True, straggler_frac=0.25,
         straggler_slowdown=2.0),                              # merge spans
], ids=["sync", "streaming-dropout", "async"])
def test_same_seed_same_span_tree(problem, kw):
    a = _traced_run(problem, _cfg(**kw))
    b = _traced_run(problem, _cfg(**kw))
    assert len(a.spans) > 0
    assert a.tree_keys() == b.tree_keys()


# ---------------------------------------------------------------------------
# The trace is the ledger: reduce_leaf ↔ leaf_costs reconciliation
# ---------------------------------------------------------------------------

def _shape_kw(shape):
    if shape == "streaming":
        return dict(upload_schedule="streaming")
    if shape == "hier":
        return dict(topology="hier", n_pods=2, inter_reducer="int8")
    return {}


@pytest.mark.parametrize("reducer", ["dense", "int8"])
@pytest.mark.parametrize("shape", ["star", "streaming", "hier"])
def test_modeled_leaf_spans_reconcile_with_hops(problem, reducer, shape):
    tr = _traced_run(problem, _cfg(reducer=reducer, **_shape_kw(shape)))
    hops = tr.find("reduce", clock=MODELED)
    leaves = tr.find("reduce_leaf", clock=MODELED)
    assert hops and leaves
    by_parent = {}
    for lf in leaves:
        by_parent.setdefault(lf.parent, []).append(lf)
    reconciled = 0
    for hop in hops:
        kids = by_parent.get(hop.id, [])
        if not kids:
            continue
        # bytes bit-exactly, seconds to float-sum precision — the same
        # invariant tests/test_streaming.py pins on the raw ledger
        assert sum(int(k.attrs["bytes"]) for k in kids) \
            == int(hop.attrs["bytes"])
        assert math.fsum(k.attrs["time_s"] for k in kids) \
            == pytest.approx(hop.attrs["time_s"], rel=1e-9, abs=1e-15)
        # leaf spans tile the hop interval back-to-back (serial α–β line)
        assert kids[0].t0 == pytest.approx(hop.t0, abs=1e-12)
        for a, b in zip(kids, kids[1:]):
            assert a.t1 == pytest.approx(b.t0, abs=1e-12)
        reconciled += 1
    assert reconciled > 0


def test_virtual_leaf_spans_match_leaf_ledger(problem):
    loss_fn, eval_fn, p0, data = problem
    cfg = _cfg(upload_schedule="streaming", straggler_frac=0.25,
               straggler_slowdown=2.0)
    tr = Tracer()
    res = runtime.run(loss_fn, p0, data, cfg, eval_fn, eval_every=8,
                      tracer=tr)
    assert res.leaf_ledger
    span_bytes = sum(int(s.attrs["bytes"])
                     for s in tr.find("reduce_leaf", clock=VIRTUAL))
    assert span_bytes == sum(int(l["bytes"]) for l in res.leaf_ledger)
    assert span_bytes == res.comm_bytes


# ---------------------------------------------------------------------------
# Metrics registry
# ---------------------------------------------------------------------------

def test_counter_gauge_histogram():
    reg = obs_metrics.MetricsRegistry()
    c = reg.counter("comm.bytes", unit="B")
    c.inc(10, reducer="dense")
    c.inc(5, reducer="dense")
    c.inc(3, reducer="int8")
    assert c.value(reducer="dense") == 15
    assert c.value(reducer="int8") == 3
    assert c.value(reducer="topk") == 0
    g = reg.gauge("train.stage_objective")
    g.set(0.5, stage=1)
    g.set(0.25, stage=1)
    assert g.value(stage=1) == 0.25
    assert g.value(stage=2) is None
    h = reg.histogram("runtime.merge_staleness")
    for v in (0.0, 1.0, 3.0):
        h.observe(v, reducer="staleness")
    s = h.summary(reducer="staleness")
    assert s["count"] == 3 and s["sum"] == 4.0
    assert s["min"] == 0.0 and s["max"] == 3.0
    assert s["mean"] == pytest.approx(4.0 / 3.0)
    assert h.summary(reducer="other") is None


def test_registry_idempotent_and_kind_checked():
    reg = obs_metrics.MetricsRegistry()
    a = reg.counter("x", unit="B")
    assert reg.counter("x") is a
    with pytest.raises(TypeError, match="already registered"):
        reg.gauge("x")
    assert "x" in reg and reg["x"] is a


def test_snapshot_is_serializable_and_sorted():
    reg = obs_metrics.MetricsRegistry()
    reg.counter("b.count").inc(2, mode="sync")
    reg.gauge("a.obj", unit="loss").set(0.5)
    reg.histogram("c.h").observe(1.0)
    snap = reg.snapshot()
    assert list(snap) == ["a.obj", "b.count", "c.h"]
    assert snap["b.count"] == {"kind": "counter", "unit": "", "help": "",
                               "values": {"mode=sync": 2.0}}
    assert snap["c.h"]["values"][""]["mean"] == 1.0
    json.dumps(snap)                      # plain data, round-trippable


def test_engine_reports_metrics_into_registry(problem):
    loss_fn, eval_fn, p0, data = problem
    runtime.run(loss_fn, p0, data, _cfg(reducer="int8"), eval_fn,
                eval_every=8)
    reg = obs_metrics.registry()
    for name in ("engine.rounds", "engine.iters", "engine.stages",
                 "comm.bytes", "comm.time_s", "train.stage_objective"):
        assert name in reg, name
    assert reg["engine.stages"].value() == 2
    assert reg["comm.bytes"].value(hop="uplink", reducer="int8") > 0


def test_async_run_populates_staleness_and_message_metrics(problem):
    loss_fn, eval_fn, p0, data = problem
    runtime.run(loss_fn, p0, data,
                _cfg(async_mode=True, straggler_frac=0.25,
                     straggler_slowdown=2.0), eval_fn, eval_every=8)
    reg = obs_metrics.registry()
    stale = reg["runtime.merge_staleness"].summary(reducer="staleness")
    assert stale is not None and stale["count"] > 0
    assert reg["comm.messages"].value(reducer="staleness") == stale["count"]
    assert reg["comm.message_bytes"].value(reducer="staleness") > 0
    assert reg["comm.merge_weight"].summary(
        reducer="staleness")["count"] == stale["count"]


# ---------------------------------------------------------------------------
# Export: Chrome trace / Perfetto, JSONL
# ---------------------------------------------------------------------------

def _toy_tracer():
    tr = Tracer(run_id="toy")
    rid = tr.begin("round", 0.0, clock=VIRTUAL, track="server",
                   attrs={"k": 2})
    tr.add("local_steps", 0.0, 2e-3, cat="compute", clock=VIRTUAL,
           track="client/0", attrs={"steps": 2})
    tr.end(rid, 3e-3)
    tr.add("reduce", 0.0, 1e-3, clock=MODELED, track="hop/uplink",
           attrs={"bytes": 128})
    with tr.span("stage", attrs={"s": 1}):
        pass
    return tr


def test_chrome_trace_structure():
    tr = _toy_tracer()
    trace = to_chrome_trace(tr)
    assert trace["otherData"]["run_id"] == "toy"
    evs = trace["traceEvents"]
    meta = [e for e in evs if e["ph"] == "M"]
    xs = [e for e in evs if e["ph"] == "X"]
    assert len(xs) == len(tr.spans)
    # one process per clock domain present in the trace
    pnames = {e["pid"]: e["args"]["name"] for e in meta
              if e["name"] == "process_name"}
    assert set(pnames) == {1, 2, 3}       # virtual, modeled, wall
    tnames = {(e["pid"], e["tid"]): e["args"]["name"] for e in meta
              if e["name"] == "thread_name"}
    assert ("client/0" in tnames.values()
            and "hop/uplink" in tnames.values())
    # µs timestamps, attrs under args, phase colors attached
    steps = next(e for e in xs if e["name"] == "local_steps")
    assert steps["ts"] == 0.0 and steps["dur"] == pytest.approx(2e3)
    assert steps["args"]["steps"] == 2 and steps["args"]["clock"] == VIRTUAL
    assert steps["cname"] == "thread_state_running"
    # wall spans are rebased to t=0
    stage = next(e for e in xs if e["name"] == "stage")
    assert stage["ts"] == pytest.approx(0.0, abs=1.0)


def test_write_chrome_trace_and_jsonl_roundtrip(tmp_path):
    tr = _toy_tracer()
    p = write_chrome_trace(tr, str(tmp_path / "t.json"))
    loaded = json.load(open(p))
    assert any(e["ph"] == "X" for e in loaded["traceEvents"])
    pl = write_jsonl(tr, str(tmp_path / "t.jsonl"))
    rows = [json.loads(line) for line in open(pl)]
    assert len(rows) == len(tr.spans)
    assert rows[0]["name"] == "round" and rows[0]["parent"] == -1


# ---------------------------------------------------------------------------
# BENCH diffing and the CLI gate
# ---------------------------------------------------------------------------

def _bench(rows, name="toy", scale="smoke"):
    return {"bench": name, "schema": 1, "meta": {"scale": scale},
            "rows": rows}


def test_validate_bench_rejects_bad_schemas():
    with pytest.raises(BenchSchemaError, match="missing required key"):
        validate_bench({"schema": 1, "rows": []})
    with pytest.raises(BenchSchemaError, match="schema version"):
        validate_bench({"bench": "x", "schema": 2, "rows": []})
    with pytest.raises(BenchSchemaError, match="rows"):
        validate_bench({"bench": "x", "schema": 1, "rows": "nope"})
    with pytest.raises(BenchSchemaError, match="not an object"):
        validate_bench({"bench": "x", "schema": 1, "rows": [3]})
    rec = validate_bench({"bench": "x", "schema": 1, "rows": []})
    assert rec["meta"] == {}


def test_diff_benches_flags_regressions_not_improvements():
    base = _bench([{"algo": "stl_sc", "reducer": "dense",
                    "comm_time_s": 1.0, "rounds": 10}])
    cur = _bench([{"algo": "stl_sc", "reducer": "dense",
                   "comm_time_s": 1.10, "rounds": 8}])
    deltas = diff_benches(base, cur)
    by_key = {d.key: d for d in deltas}
    assert by_key["comm_time_s"].regressed(0.05)
    assert not by_key["comm_time_s"].regressed(0.15)
    assert by_key["rounds"].improved(0.05)
    assert not by_key["rounds"].regressed(0.05)
    assert by_key["comm_time_s"].ratio == pytest.approx(1.10)
    # unmatched rows and missing columns contribute nothing
    assert not diff_benches(base, _bench([{"algo": "other",
                                           "comm_time_s": 9.0}]))
    assert not diff_benches(base, _bench([{"algo": "stl_sc",
                                           "reducer": "dense"}]))


def _write_bench_dir(d, rows, scale="smoke"):
    d.mkdir(parents=True, exist_ok=True)
    (d / "BENCH_toy.json").write_text(json.dumps(_bench(rows, scale=scale)))


def test_diff_dirs_scale_mismatch_skips(tmp_path):
    row = [{"algo": "a", "comm_bytes": 100}]
    _write_bench_dir(tmp_path / "base", row, scale="full")
    _write_bench_dir(tmp_path / "cur", row, scale="smoke")
    dd = diff_dirs(str(tmp_path / "base"), str(tmp_path / "cur"))
    assert not dd.compared and not dd.deltas
    assert any("scale mismatch" in s for s in dd.skipped)


def test_diff_dirs_reports_baseline_only(tmp_path):
    _write_bench_dir(tmp_path / "base", [{"algo": "a", "rounds": 1}])
    (tmp_path / "cur").mkdir()
    dd = diff_dirs(str(tmp_path / "base"), str(tmp_path / "cur"))
    assert any("baseline only" in s for s in dd.skipped)


def _bench_diff_cli(*argv):
    return subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "bench_diff.py"),
         *argv], capture_output=True, text=True)


def test_bench_diff_cli_exit_codes(tmp_path):
    base, cur = tmp_path / "base", tmp_path / "cur"
    row = [{"algo": "stl_sc", "reducer": "dense", "comm_time_s": 1.0,
            "comm_bytes": 1000}]
    _write_bench_dir(base, row)
    _write_bench_dir(cur, row)
    ok = _bench_diff_cli(str(base), str(cur))
    assert ok.returncode == 0, ok.stderr
    assert "0 regression(s)" in ok.stdout
    # inject a 10% modeled-seconds regression: must fail the 5% gate
    _write_bench_dir(cur, [dict(row[0], comm_time_s=1.10)])
    bad = _bench_diff_cli(str(base), str(cur))
    assert bad.returncode == 1
    assert "REGRESSED" in bad.stdout and "comm_time_s" in bad.stdout
    # ...and pass a looser one
    assert _bench_diff_cli(str(base), str(cur), "--tol", "0.2") \
        .returncode == 0
    # schema violations are usage errors, not regressions
    (base / "BENCH_toy.json").write_text('{"rows": []}')
    err = _bench_diff_cli(str(base), str(cur))
    assert err.returncode == 2 and "missing required key" in err.stderr
    # missing baseline dir
    assert _bench_diff_cli(str(tmp_path / "nope"), str(cur)) \
        .returncode == 2


# ---------------------------------------------------------------------------
# Structured logging and the sync step's topology
# ---------------------------------------------------------------------------

def test_structured_logger_jsonl_and_levels():
    out = io.StringIO()
    log = StructuredLogger("t", stream=out, level="info", run_id="r1")
    log.debug("hidden", x=1)
    log.info("stage_done", stage=2, loss=0.5)
    rec = json.loads(out.getvalue())
    assert rec["event"] == "stage_done" and rec["stage"] == 2
    assert rec["level"] == "info" and rec["logger"] == "t"
    assert rec["run_id"] == "r1" and "mono_s" in rec
    assert "virtual_time_s" not in rec


def test_structured_logger_printf_compat_and_clock():
    out = io.StringIO()
    log = StructuredLogger("t", stream=out, level="info")
    class _Clk:
        now = 1.25
    log.bind_clock(_Clk())
    log.info("arch=%s clients=%d", "toy", 4)
    rec = json.loads(out.getvalue())
    assert rec["event"] == "log" and rec["msg"] == "arch=toy clients=4"
    assert rec["virtual_time_s"] == 1.25
    out.truncate(0), out.seek(0)
    log.quiet().error("anything")
    assert out.getvalue() == ""


def test_sync_step_tags_survive_jit():
    step = build_sync_step("int8", streaming=True)
    topo = sync_step_topology(jax.jit(step))
    # the step carries the Topology it runs, built Reducer objects and all
    # (the driver prices the exact instance the round transmits)
    assert topo is step.topology
    assert topo.reducer.name == "int8" and topo.streaming
    assert not isinstance(topo, Hierarchical)
    hier = build_sync_step("dense", hierarchical=True, n_pods=2,
                           inter_reducer="int8")
    topo = sync_step_topology(hier)
    assert isinstance(topo, Hierarchical) and topo.n_pods == 2
    assert topo.inter.name == "int8"
