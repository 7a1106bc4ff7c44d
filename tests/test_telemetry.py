"""Time-series telemetry, SLO monitoring and modeled-vs-measured profiling.

The decisive invariants of the trajectory half of ``repro.obs``:
  * the clock-domain guard is strict: re-registering a series name on a
    different clock (or an unknown clock) raises ``ClockDomainError``
    instead of silently interleaving timelines;
  * same (config, seed) ⇒ bit-identical series fingerprints across
    repeated runs, for the synchronous and asynchronous event runtimes,
    and between traced and untraced engine runs (the modeled cursor is
    one arithmetic path either way);
  * series export as Perfetto counter tracks whose timestamps align with
    the span timestamps of the same clock's process;
  * the SLO monitor turns windowed aggregates into breach intervals:
    synthetic breaches are detected, recovery closes them, an open
    breach at trace end reads as saturation, and intervals export as
    ``slo_breach`` spans on the virtual clock;
  * ``ProfileSession`` reconciles: every profiled span carries both
    modeled and measured seconds, span durations equal the recorded
    measured times, and wrapping never hides the topology a
    ``build_sync_step`` round carries;
  * histogram percentiles are numpy-exact below ``cap`` and degrade to a
    flagged, deterministic reservoir above it;
  * ``read_jsonl`` round-trips ``write_jsonl`` span logs;
  * ``StructuredLogger.limit`` samples/rate-limits without silent drops.
"""
import io
import json
import math

import jax.numpy as jnp
import numpy as np
import pytest

from repro import runtime
from repro.core.local_sgd import build_sync_step, sync_step_topology
from repro.core.stl_sgd import StagewiseDriver, driver_state
from repro.obs import (
    MODELED,
    VIRTUAL,
    WALL,
    ClockDomainError,
    ProfileSession,
    Series,
    SeriesRegistry,
    SLOMonitor,
    SLOTarget,
    Tracer,
    format_skew_table,
    read_jsonl,
    serve_slo_targets,
    span_record,
    to_chrome_trace,
    write_jsonl,
)
from repro.obs import metrics as obs_metrics
from repro.obs import series as obs_series
from repro.utils.logging import StructuredLogger

from tests.test_obs import _cfg, problem  # noqa: F401 (fixture)


@pytest.fixture(autouse=True)
def _fresh_registries():
    obs_metrics.reset()
    obs_series.reset()
    yield
    obs_metrics.reset()
    obs_series.reset()


# ---------------------------------------------------------------------------
# Series primitives: clock guard, windowed views, bounded memory
# ---------------------------------------------------------------------------

def test_clock_domain_guard_raises():
    reg = SeriesRegistry()
    s = reg.series("q.depth", VIRTUAL, unit="requests")
    assert reg.series("q.depth", VIRTUAL) is s          # idempotent
    with pytest.raises(ClockDomainError):
        reg.series("q.depth", MODELED)
    with pytest.raises(ClockDomainError):
        reg.add(Series("q.depth", WALL))
    with pytest.raises(ClockDomainError):
        Series("bogus", "gpu-clock")


def test_series_sorts_lazily_and_stably():
    s = Series("lat", VIRTUAL)
    for t, v in [(3.0, 30.0), (1.0, 10.0), (2.0, 20.0), (1.0, 11.0)]:
        s.record(t, v)
    assert s.samples() == [(1.0, 10.0), (1.0, 11.0), (2.0, 20.0),
                           (3.0, 30.0)]
    assert s.last() == (3.0, 30.0)


def test_series_max_samples_drops_deterministically():
    s = Series("bounded", VIRTUAL, max_samples=3)
    for i in range(5):
        s.record(float(i), float(i))
    assert len(s) == 3
    assert s.values() == [0.0, 1.0, 2.0]                # keep-first
    assert s.dropped == 2
    assert s.snapshot()["summary"]["dropped"] == 2
    assert s.fingerprint()[-1] == 2                     # drops are identity


def test_windowed_views_match_brute_force():
    ts = [float(i) for i in range(10)]
    vs = [3.0, 1.0, 4.0, 1.0, 5.0, 9.0, 2.0, 6.0, 5.0, 3.0]
    s = Series("x", VIRTUAL)
    for t, v in zip(ts, vs):
        s.record(t, v)
    w = 3.0
    mean = s.window_mean(w)
    p95 = s.window_percentile(95, w)
    assert mean.clock == p95.clock == VIRTUAL
    for (t, m), (_, p) in zip(mean.samples(), p95.samples()):
        window = [v for tt, v in zip(ts, vs) if t - w < tt <= t]
        assert m == pytest.approx(sum(window) / len(window), rel=1e-12)
        assert p == pytest.approx(np.percentile(window, 95), rel=1e-12)
    # min_count delays percentile emission until the window fills
    late = s.window_percentile(50, w, min_count=3)
    assert late.times() == ts[2:]


def test_rate_of_cumulative_counter():
    s = Series("tokens", VIRTUAL, unit="tokens")
    for i in range(8):
        s.record(float(i), 2.0 * i)
    r = s.rate(4.0)
    assert r.unit == "tokens/s"
    assert r.times() == [float(i) for i in range(1, 8)]  # t=0: zero-span
    assert all(v == pytest.approx(2.0) for v in r.values())


# ---------------------------------------------------------------------------
# Determinism: same seed ⇒ identical series, traced or not
# ---------------------------------------------------------------------------

def _series_run(problem, cfg, tracer=None):
    loss_fn, eval_fn, p0, data = problem
    reg = SeriesRegistry()
    runtime.run(loss_fn, p0, data, cfg, eval_fn, eval_every=8,
                tracer=tracer, series=reg)
    return reg


@pytest.mark.parametrize("kw,expected", [
    (dict(), ["comm.round_bytes", "comm.round_time_s", "comm.cum_bytes",
              "train.stage_bytes", "runtime.active_clients",
              "runtime.round_time_s"]),
    (dict(async_mode=True, straggler_frac=0.25, straggler_slowdown=2.0),
     ["runtime.active_clients", "runtime.inflight_merges",
      "runtime.merge_staleness"]),
], ids=["sync", "async"])
def test_same_seed_same_series(problem, kw, expected):
    cfg = _cfg(**kw)
    a = _series_run(problem, cfg)
    b = _series_run(problem, cfg)
    for name in expected:
        assert name in a, f"missing series {name}: {a.names()}"
        assert len(a[name]) > 0
    assert a.fingerprint() == b.fingerprint()


def test_engine_series_identical_traced_vs_untraced(problem):
    cfg = _cfg()
    tr = Tracer()
    traced = _series_run(problem, cfg, tracer=tr)
    untraced = _series_run(problem, cfg)
    assert traced.fingerprint() == untraced.fingerprint()
    # the comm.* sample times ARE the round-span end times: one
    # arithmetic path moves the modeled cursor whether or not spans exist
    rounds = tr.find("round", clock=MODELED)
    s_time = traced["comm.round_time_s"]
    assert s_time.clock == MODELED
    assert s_time.times() == [r.t1 for r in rounds]
    assert s_time.values() == [r.t1 - r.t0 for r in rounds]
    # cumulative bytes is the running sum of per-round bytes, bit-exactly
    cum = traced["comm.cum_bytes"].values()
    per = traced["comm.round_bytes"].values()
    assert cum == [float(sum(per[:i + 1])) for i in range(len(per))]


def test_stage_objective_vs_bytes_curve(problem):
    reg = _series_run(problem, _cfg())
    obj, byt = reg["train.stage_objective"], reg["train.stage_bytes"]
    assert obj.clock == byt.clock == MODELED
    assert len(obj) == len(byt) == 2                    # one per stage
    assert obj.times() == byt.times()                   # same boundaries
    assert byt.values() == sorted(byt.values())         # bytes accumulate


# ---------------------------------------------------------------------------
# Counter tracks: series render as "C" events aligned with spans
# ---------------------------------------------------------------------------

def test_counter_tracks_align_with_spans(problem):
    tr = Tracer(run_id="ct")
    reg = _series_run(problem, _cfg(), tracer=tr)
    trace = to_chrome_trace(tr, series=reg)
    counters = [e for e in trace["traceEvents"] if e["ph"] == "C"]
    by_name = {}
    for e in counters:
        by_name.setdefault(e["name"], []).append(e)
    assert set(reg.names()) <= set(by_name)
    # every sample became one C event at its own (µs) timestamp, in the
    # process of its clock — modeled pid 2 here, same as the round spans
    s = reg["comm.round_time_s"]
    evs = by_name["comm.round_time_s"]
    assert [e["ts"] for e in evs] == [t * 1e6 for t in s.times()]
    assert [e["args"]["value"] for e in evs] == s.values()
    round_ev = next(e for e in trace["traceEvents"]
                    if e.get("ph") == "X" and e["name"] == "round"
                    and e["args"]["clock"] == MODELED)
    assert evs[0]["pid"] == round_ev["pid"]
    assert json.dumps(trace)                            # serializable


def test_wall_series_rebased_like_wall_spans():
    tr = Tracer(run_id="w")
    tr.add("step", 100.0, 101.0, clock=WALL, track="host")
    s = Series("host.rss", WALL, unit="B")
    s.record(100.5, 7.0)
    trace = to_chrome_trace(tr, series=[s])
    c = next(e for e in trace["traceEvents"] if e["ph"] == "C")
    x = next(e for e in trace["traceEvents"] if e["ph"] == "X")
    assert x["ts"] == 0.0                               # rebased to wall0
    assert c["ts"] == pytest.approx(0.5e6)


# ---------------------------------------------------------------------------
# SLO monitor: synthetic breaches, recovery, saturation
# ---------------------------------------------------------------------------

def _ttft_series(reg, samples):
    s = reg.series("serve.ttft_s", VIRTUAL, unit="s")
    for t, v in samples:
        s.record(t, v)
    return s


def test_slo_detects_breach_and_recovery():
    reg = SeriesRegistry()
    good = [(float(t), 1.0) for t in range(6)]
    bad = [(float(t), 20.0) for t in range(10, 14)]
    _ttft_series(reg, good + bad + [(20.0, 1.0)])
    targets = serve_slo_targets(1.0, window_steps=4.0, min_count=1)
    mon = SLOMonitor(targets)
    breaches = mon.evaluate(reg)
    assert [b.target for b in breaches] == ["ttft_p95"]
    b = breaches[0]
    assert (b.t0, b.t1, b.worst, b.open) == (10.0, 13.0, 20.0, False)
    assert mon.time_to_breach() == 10.0
    assert mon.breach_seconds() == 3.0
    assert not mon.saturated()                          # recovered by t=20
    tr = Tracer()
    mon.emit_spans(tr)
    span = tr.find("slo_breach", clock=VIRTUAL)[0]
    assert (span.t0, span.t1) == (10.0, 13.0)
    assert span.attrs["target"] == "ttft_p95"
    assert span.attrs["open"] is False


def test_slo_open_breach_reads_as_saturated():
    reg = SeriesRegistry()
    _ttft_series(reg, [(float(t), 1.0) for t in range(4)]
                 + [(float(t), 50.0) for t in range(10, 14)])
    mon = SLOMonitor(serve_slo_targets(1.0, window_steps=4.0, min_count=1))
    mon.evaluate(reg)
    assert mon.saturated()
    assert mon.breaches[-1].open


def test_slo_clean_run_and_partial_telemetry():
    reg = SeriesRegistry()
    _ttft_series(reg, [(float(t), 1.0) for t in range(8)])
    # e2e/tokens series absent: targets over them contribute nothing
    mon = SLOMonitor(serve_slo_targets(1.0, tok_s_floor=1.0))
    assert mon.evaluate(reg) == []
    assert mon.time_to_breach() is None
    assert mon.breach_seconds() == 0.0
    assert not mon.saturated()
    assert mon.summary()["n_breaches"] == 0


def test_slo_throughput_floor_breaches_from_below():
    reg = SeriesRegistry()
    tok = reg.series("serve.tokens_total", VIRTUAL, unit="tokens")
    for i in range(8):
        tok.record(float(i), float(i))                  # 1 token/s
    targets = serve_slo_targets(1.0, window_steps=4.0,
                                tok_s_floor=10.0)
    mon = SLOMonitor(targets)
    mon.evaluate(reg)
    floor = [b for b in mon.breaches if b.target == "tok_s_min"]
    assert floor and floor[-1].open                     # never recovers
    assert floor[0].worst == pytest.approx(1.0)


def test_slo_targets_scale_with_decode_step():
    fast = serve_slo_targets(1e-6)
    slow = serve_slo_targets(1e-3)
    for f, s in zip(fast, slow):
        assert s.threshold == pytest.approx(1e3 * f.threshold)
        assert s.window_s == pytest.approx(1e3 * f.window_s)
    with pytest.raises(ValueError):
        SLOTarget("bad", "serve.ttft_s", "p42", 1.0, 1.0)


# ---------------------------------------------------------------------------
# ProfileSession: modeled-vs-measured reconciliation on a toy driver
# ---------------------------------------------------------------------------

def _toy_driver(profile):
    def train_fn(state, batch, eta):
        return dict(state, step=state["step"] + 1), {"loss": 0.5}

    sync_fn = lambda state: state
    tcfg = _cfg(T1=4, n_stages=2)
    train_w = profile.wrap(train_fn, "train_step", 1e-3)
    sync_w = profile.wrap(sync_fn, "sync_step", lambda *a, **k: 2e-3)
    return StagewiseDriver(tcfg, train_w, sync_w)


def test_profile_skew_table_reconciles():
    import itertools

    prof = ProfileSession()
    driver = _toy_driver(prof)
    state = driver_state({"w": jnp.ones((8,), jnp.float32)}, 4)
    with prof:
        ds = driver.run(state, itertools.repeat(None), max_iters=12)
    assert ds.iters_total == 12
    rows = {r["name"]: r for r in prof.skew_table()}
    assert set(rows) == {"train_step", "sync_step"}
    # every profiled call carries BOTH timelines; totals reconcile
    assert rows["train_step"]["calls"] == 12
    assert rows["train_step"]["modeled_s"] == pytest.approx(12e-3)
    assert rows["sync_step"]["modeled_s"] == pytest.approx(
        rows["sync_step"]["calls"] * 2e-3)
    for r in rows.values():
        assert r["measured_s"] >= 0.0
        assert r["skew"] == r["measured_s"] / r["modeled_s"]
    # emit_spans: wall-clock profile.<name> spans, durations equal to the
    # measured seconds bit-exactly, attrs carrying both timelines
    tr = Tracer()
    prof.emit_spans(tr)
    spans = tr.find("profile.train_step") + tr.find("profile.sync_step")
    assert len(spans) == len(prof.records)
    for sp in spans:
        assert sp.clock == WALL
        assert "modeled_s" in sp.attrs and "measured_s" in sp.attrs
        assert sp.key()[6:8] == (None, None)            # wall ts excluded
    assert math.fsum(sp.t1 - sp.t0 for sp in spans) \
        == math.fsum(r.measured_s for r in prof.records)
    table = format_skew_table(prof.skew_table())
    assert "train_step" in table and "skew" in table
    assert format_skew_table([]) == "(no profiled steps)"


def test_profile_wrap_preserves_sync_step_tags():
    import jax

    raw = build_sync_step("int8")
    prof = ProfileSession()
    wrapped = prof.wrap(jax.jit(raw), "sync_step", 1e-3)
    assert sync_step_topology(wrapped) is sync_step_topology(raw)
    assert sync_step_topology(wrapped).reducer.name == "int8"


def test_profile_session_raises_when_profiler_cannot_start(tmp_path):
    """A trace session that cannot start is an error, never silently a
    run without its trace."""
    import jax

    jax.profiler.start_trace(str(tmp_path / "a"))
    try:
        with pytest.raises(RuntimeError):
            with ProfileSession(logdir=str(tmp_path / "b")):
                pass
    finally:
        jax.profiler.stop_trace()


def test_profile_session_without_logdir_is_harmless():
    prof = ProfileSession()                             # no jax.profiler
    with prof:
        out = prof.step("f", 0.5, lambda a, b: a + b, 2, 3)
    assert out == 5
    (r,) = prof.records
    assert r.modeled_s == 0.5 and r.t1 >= r.t0
    assert r.measured_s == r.t1 - r.t0


# ---------------------------------------------------------------------------
# Histogram reservoir: exact below cap, flagged + deterministic above
# ---------------------------------------------------------------------------

def test_histogram_exact_below_cap():
    rng = np.random.RandomState(0)
    vals = rng.exponential(size=200).tolist()
    h = obs_metrics.registry().histogram("lat.exact", unit="s")
    for v in vals:
        h.observe(v)
    s = h.summary()
    assert s["approx"] is False
    assert s["count"] == 200
    for q in (50, 95, 99):
        assert s[f"p{q}"] == pytest.approx(np.percentile(vals, q),
                                           rel=1e-12)


def test_histogram_reservoir_above_cap():
    vals = [float(i) for i in range(1000)]

    def fill(reg):
        h = reg.histogram("lat.capped", unit="s", cap=16)
        for v in vals:
            h.observe(v)
        return h

    h1, h2 = fill(obs_metrics.MetricsRegistry()), \
        fill(obs_metrics.MetricsRegistry())
    s = h1.summary()
    assert s["approx"] is True
    assert s["count"] == 1000 and s["max"] == 999.0     # stats stay exact
    assert s["sum"] == pytest.approx(sum(vals))
    assert len(h1.samples[()]) == 16
    # the reservoir is seeded per (metric, label set): runs agree bit-wise
    assert h1.samples[()] == h2.samples[()]


def test_serve_ledger_pins_cap_above_sample_counts():
    from repro.serve.ledger import LATENCY_SAMPLE_CAP

    assert LATENCY_SAMPLE_CAP >= 4096                   # table6 stays exact


# ---------------------------------------------------------------------------
# JSONL round-trip
# ---------------------------------------------------------------------------

def test_read_jsonl_round_trips(tmp_path):
    tr = Tracer(run_id="rt")
    rid = tr.begin("round", 0.0, clock=MODELED, track="round",
                   attrs={"k": 2})
    tr.add("reduce", 0.0, 1.5, clock=MODELED, track="hop/0",
           attrs={"bytes": 4096, "reducer": "int8"})
    tr.end(rid, 2.0)
    tr.add("merge", 0.25, 0.5, clock=VIRTUAL, track="server",
           attrs={"staleness": 0.125})
    path = str(tmp_path / "spans.jsonl")
    write_jsonl(tr, path)
    back = read_jsonl(path)
    assert [span_record(s) for s in back] \
        == [span_record(s) for s in tr.spans]
    assert [s.key() for s in back] == [s.key() for s in tr.spans]
    # a re-exported trace is identical to the original's
    assert to_chrome_trace(back) == to_chrome_trace(tr.spans)


# ---------------------------------------------------------------------------
# Logger sampling / rate limiting: never silent
# ---------------------------------------------------------------------------

def test_logger_every_n_counts_drops():
    buf = io.StringIO()
    log = StructuredLogger("lim", stream=buf, level="debug").limit(every_n=3)
    recs = [log.info("tick", i=i) for i in range(7)]
    emitted = [r for r in recs if r is not None]
    assert [r["i"] for r in emitted] == [0, 3, 6]
    # drops surface on the NEXT emitted record, cumulatively since last
    assert "dropped" not in emitted[0]
    assert emitted[1]["dropped"] == emitted[2]["dropped"] == 2
    assert log.dropped_total == 4
    assert obs_metrics.registry()["log.dropped_lines"].value(logger="lim") \
        == 4
    assert len(buf.getvalue().strip().splitlines()) == 3
    # warnings bypass the limiter and don't consume the sample sequence
    assert log.warning("uhoh") is not None
    assert log.info("tick", i=7) is None                # 8th info: dropped


def test_logger_max_per_s_on_virtual_clock():
    class FakeClock:
        now = 0.0

    clk = FakeClock()
    buf = io.StringIO()
    log = (StructuredLogger("rps", stream=buf, level="debug")
           .bind_clock(clk).limit(max_per_s=2.0))     # 0.5 s buckets
    out = []
    for t in (0.0, 0.1, 0.2, 0.6, 0.7, 2.0):
        clk.now = t
        out.append(log.info("ev", t=t))
    assert [r["t"] for r in out if r] == [0.0, 0.6, 2.0]
    assert out[3]["dropped"] == 2
    assert all(r is None or r["virtual_time_s"] == r["t"] for r in out)
    # limit() with no args clears both limiters
    log.limit()
    assert log.info("ev", t=99.0) is not None


def test_logger_unlimited_by_default():
    buf = io.StringIO()
    log = StructuredLogger("free", stream=buf, level="debug")
    assert all(log.info("ev", i=i) is not None for i in range(5))
    assert log.dropped_total == 0
