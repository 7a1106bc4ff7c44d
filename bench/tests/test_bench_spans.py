"""The readers of the training loop's own spans: the idle-splitting
arithmetic on made-up intervals, the ``stl.*`` tree a tiny cell's cycle
writes under the profiler on the CPU, and the split on a small trace
recorded on a v5e chip by a program that wrote no ``stl.*`` span."""
import gzip
from pathlib import Path

import pytest

from bench_tiny import PEAKS, tiny_cell
from bench.harness import cli, spec, trace
from bench.metrics import (_spans, dispatch_idle_ms, host_sync_idle_ms,
                           host_syncs_per_step, input_wait_ms)

DATA = Path(__file__).resolve().parent / "data"
IDLE_READERS = (input_wait_ms, dispatch_idle_ms, host_sync_idle_ms)


def test_segments_nested_and_outside():
    spans = [("stl.step", 10, 50), ("stl.input", 10, 20),
             ("stl.dispatch", 20, 30), ("stl.wait", 35, 50)]
    assert _spans.segments(spans, 0, 60) == [
        (0, 10, None), (10, 20, "stl.input"), (20, 30, "stl.dispatch"),
        (30, 35, "stl.step"), (35, 50, "stl.wait"), (50, 60, None)]
    # clipped to the window
    assert _spans.segments(spans, 15, 40) == [
        (15, 20, "stl.input"), (20, 30, "stl.dispatch"),
        (30, 35, "stl.step"), (35, 40, "stl.wait")]
    assert _spans.segments([], 0, 5) == [(0, 5, None)]


def test_split_idle_exact_at_boundaries():
    spans = [("stl.run", 0, 100), ("stl.step", 10, 50),
             ("stl.input", 10, 20), ("stl.dispatch", 20, 30),
             ("stl.wait", 35, 50)]
    pieces = _spans.segments(spans, -10, 110)
    gaps = [(-5, 2),      # outside every span, then under stl.run
            (18, 22),     # across input -> dispatch
            (28, 40),     # dispatch -> step -> wait
            (60, 61),     # under stl.run only
            (99, 105)]    # stl.run -> outside
    got = _spans.split_idle(gaps, pieces)
    assert got == {None: 5 + 5, "stl.run": 2 + 1 + 1, "stl.input": 2,
                   "stl.dispatch": 2 + 2, "stl.step": 5, "stl.wait": 5}
    assert sum(got.values()) == sum(e - s for s, e in gaps)


def _inside(events, outer):
    """The sorted names of the other events within ``outer``."""
    return sorted(n for n, s, e, _ in events
                  if (s, e) != outer[1:3] and outer[1] <= s and e <= outer[2])


@pytest.fixture(scope="module")
def cpu_cycle(tmp_path_factory):
    """One driver cycle of the tiny cell (2 stages: 4 steps and 2 rounds,
    then 8 steps and 2 rounds) under ``jax.profiler`` on the CPU."""
    import jax
    from jax.profiler import TraceAnnotation

    from bench.harness.train import TrainCell

    cell = tiny_cell()
    tc = TrainCell(cell.config, cell.traffic, jax.devices()[:1])
    state, _ = tc.first_cycle(3)
    logdir = tmp_path_factory.mktemp("trace")
    jax.profiler.start_trace(str(logdir))
    with TraceAnnotation(trace.WINDOW):
        ds = tc.driver.run(state, tc.feed())
        jax.block_until_ready(ds.state)
    jax.profiler.stop_trace()
    pd = trace.load(trace.find_xplane(str(logdir)))
    (t0, t1), line = _spans._driver_line(pd)
    events = [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns,
               dict(ev.stats)) for ev in line.events
              if ev.name.startswith("stl.") and t0 <= ev.start_ns <= t1]
    return logdir, ds, events, tc


def test_cpu_cycle_span_tree(cpu_cycle):
    _, ds, events, tc = cpu_cycle
    inside = lambda ev: _inside(events, ev)  # noqa: E731
    by = lambda n: [ev for ev in events if ev[0] == n]  # noqa: E731
    steps, rounds = by("stl.step"), by("stl.reduce")
    assert len(steps) == ds.iters_total == tc.cycle_steps == 12
    assert len(rounds) == ds.rounds_total == tc.cycle_rounds == 4
    assert [ev[3]["step_num"] for ev in steps] == list(range(12))
    for ev in steps:
        assert inside(ev) == ["stl.dispatch", "stl.input", "stl.loss_read",
                              "stl.wait"]
    for ev in rounds:
        assert inside(ev) == ["stl.dispatch", "stl.wait"]
    ends = by("stl.stage_end")
    assert len(ends) == len(by("stl.stage")) == 2
    assert all(inside(ev) == ["stl.loss_read"] for ev in ends)
    (run,) = by("stl.run")
    assert len(inside(run)) == len(events) - 1
    for ev in by("stl.local_steps"):
        assert set(inside(ev)) == {"stl.step", "stl.input", "stl.dispatch",
                                   "stl.wait", "stl.loss_read"}


def test_cpu_cycle_host_syncs_per_step(cpu_cycle, monkeypatch):
    logdir, ds, _, _ = cpu_cycle
    monkeypatch.setattr(cli, "TRACE_DIR", logdir)
    ctx = cli.TraceContext(trace={}, chips=1, peaks=PEAKS,
                           steps=ds.iters_total, rounds=ds.rounds_total,
                           step_flops=0.0, round_bytes=0)
    # a wait and a loss read per step, a wait per round, a loss read per
    # stage end
    assert host_syncs_per_step.read(ctx) == (2 * 12 + 4 + 2) / 12
    # no device plane on the CPU: nothing to split
    assert all(r.read(ctx) is None for r in IDLE_READERS)


def test_schedule_counts_of_the_cells():
    """The exact counts the accepted cells read: (48+8+2)/24 on one chip,
    (48+16+2)/24 on four."""
    from repro.configs.base import TrainConfig
    from repro.engine.algorithm import get_algorithm

    for traffic, want in (("stl2x2048", 58 / 24), ("flat4-k1", 66 / 24)):
        t = spec._json("traffic", traffic)
        stages = get_algorithm(t["algo"]).stages(TrainConfig(
            algo=t["algo"], eta1=t["eta1"], k1=t["k1"], T1=t["T1"],
            n_stages=t["n_stages"]))
        steps = sum(s.T for s in stages)
        rounds = sum(-(-s.T // int(s.k)) for s in stages)
        assert (2 * steps + rounds + len(stages)) / steps == want


def test_chip_trace_without_stl_spans(tmp_path, monkeypatch):
    """The PR 12 chip sample: a program that writes no ``stl.*`` span
    reads as nothing (the readers stay silent and do not raise); split by
    the harness's own ``bench.*`` spans instead, its TPU plane's idle time
    is put down in full, to the nanosecond the reduction counts."""
    (tmp_path / "old.xplane.pb").write_bytes(
        gzip.decompress((DATA / "sample.xplane.pb.gz").read_bytes()))
    monkeypatch.setattr(cli, "TRACE_DIR", tmp_path)
    pd = trace.load(trace.find_xplane(str(tmp_path)))
    ctx = cli.TraceContext(trace={}, chips=1, peaks=PEAKS, steps=2, rounds=2,
                           step_flops=0.0, round_bytes=0)
    assert _spans.cycle() is None
    assert all(r.read(ctx) is None
               for r in IDLE_READERS + (host_syncs_per_step,))
    monkeypatch.setattr(_spans, "PREFIX", "bench.")
    (d,) = _spans.summarise(pd)["devices"]
    red = trace.reduce(pd)
    idle_ns = (red["window_s"] - red["busy_s"]) * 1e9
    assert d["idle_ns"] == pytest.approx(idle_ns, rel=1e-12)
    assert sum(d["by_span"].values()) == pytest.approx(idle_ns, rel=1e-12)
    assert None not in d["by_span"]   # the window is itself a bench.* span
    assert set(d["by_span"]) <= {"bench.traced", "bench.fetch",
                                 "bench.local_step", "bench.sync_round"}
