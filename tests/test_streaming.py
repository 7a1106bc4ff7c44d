"""Per-leaf streaming reduce: schedules, topology, ledger, execution.

The decisive invariants:
  * streaming is pure clock accounting — same config + seed produces
    bit-identical parameters and (round, objective) trajectories under
    the blocking and streaming upload schedules; only modeled wall-clock
    changes (and only shrinks);
  * a single-leaf model cannot overlap anything: its streaming and
    blocking round prices are identical;
  * the per-leaf comm ledger reconciles with the tree-level totals —
    bytes bit-exactly, modeled seconds to float-sum precision — for
    dense and int8 reducers, star and hierarchical topologies;
  * ``StreamingStar``'s per-leaf reduce and
    ``build_sync_step(streaming=True)``'s per-leaf round are bit-exact
    with their blocking counterparts (same per-leaf rng folds);
  * the StagewiseDriver accepts the streaming topology and carries the
    per-leaf ledger; asynchronous merging rejects streaming uploads.
"""
import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import runtime
from repro.comm import NetworkModel, get_reducer
from repro.configs.base import TrainConfig
from repro.core import local_sgd as LS
from repro.core import simulate
from repro.data import make_binary_classification, partition_iid
from repro.engine import Star, StreamingStar, get_topology
from repro.models import logreg, mlp
from repro.runtime import (
    BlockingSchedule,
    ClientProcess,
    StreamingSchedule,
    get_schedule,
)
from repro.utils.tree import tree_broadcast_leading, tree_mean_leading


def _tree_equal(a, b):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


@pytest.fixture(scope="module")
def mlp_problem():
    x, y = make_binary_classification(n=512, d=96, seed=0)
    lam = 1e-3
    data = {k: jnp.asarray(v)
            for k, v in partition_iid(x, y, 8, seed=1).items()}
    xj, yj = jnp.asarray(x), jnp.asarray(y)
    loss_fn = lambda p, b: mlp.loss_fn(p, b, lam)
    eval_fn = jax.jit(lambda p: mlp.full_objective(p, xj, yj, lam))
    return loss_fn, eval_fn, mlp.init_params(jax.random.key(42), 96), data


def _stream_cfg(**kw):
    base = dict(algo="sync", eta1=0.1, T1=16, n_stages=2,
                batch_per_client=16, seed=0,
                comm_latency_s=1e-4, comm_bandwidth_gbps=0.45,
                base_step_time_s=1e-3,
                straggler_frac=0.25, straggler_slowdown=2.0)
    base.update(kw)
    return TrainConfig(**base)


# ---------------------------------------------------------------------------
# Upload schedule unit tests (pure clock arithmetic)
# ---------------------------------------------------------------------------

def _client(step_s=1e-3, alpha=1e-4, gbps=0.8):
    return ClientProcess(cid=0, rate=1.0, step_time_s=step_s,
                         network=NetworkModel(latency_s=alpha,
                                              bandwidth_gbps=gbps))


def test_blocking_schedule_events():
    c = _client()
    evs, fin = BlockingSchedule().round_events(c, 1.0, 2, [4000, 4000],
                                               [0.5, 0.5])
    assert [k for _, k, _ in evs] == ["compute_done", "arrival"]
    assert evs[0][0] == pytest.approx(1.0 + 2e-3)
    # arrival = compute_done + alpha + total_bytes / bandwidth
    assert fin == pytest.approx(1.0 + 2e-3 + 1e-4 + 8000 / 1e8)
    # dropped client: upload-only zero-delta answer from round start
    evs, fin = BlockingSchedule().round_events(c, 1.0, 2, [4000, 4000],
                                               [0.5, 0.5], active=False)
    assert [k for _, k, _ in evs] == ["arrival"]
    assert fin == pytest.approx(1.0 + 1e-4 + 8000 / 1e8)


def test_streaming_schedule_reverse_order_and_link_queue():
    """Leaves release in reverse order spread across the final step; the
    uplink is one serial stream (alpha once, leaves queue when the link is
    busy)."""
    c = _client()  # step 1 ms, alpha 0.1 ms, 1e8 B/s
    sched = StreamingSchedule()
    evs, fin = sched.round_events(c, 0.0, 2, [4000, 4000], [0.5, 0.5])
    kinds = [k for _, k, _ in evs]
    assert kinds == ["compute_done", "leaf_arrival", "leaf_arrival"]
    # leaf 1 (last layer) releases halfway through the final step
    # [1 ms, 2 ms] => ready 1.5 ms, +alpha +4000B/1e8 = 1.64 ms
    assert evs[1][2] == (1,)
    assert evs[1][0] == pytest.approx(1.5e-3 + 1e-4 + 4e-5)
    # leaf 0 releases at compute_done (2 ms), link already free => 2.04 ms
    assert evs[2][2] == (0,)
    assert evs[2][0] == pytest.approx(2e-3 + 4e-5)
    assert fin == pytest.approx(2e-3 + 4e-5)
    # vs blocking: 2 ms + 0.1 ms + 8e-5 s = 2.18 ms — streaming wins
    _, fin_b = BlockingSchedule().round_events(c, 0.0, 2, [4000, 4000],
                                               [0.5, 0.5])
    assert fin < fin_b

    # link-bound regime: big payloads queue back-to-back behind the stream
    evs, fin = sched.round_events(c, 0.0, 1, [40000, 40000], [0.5, 0.5])
    # leaf 1 ready 0.5 ms, fin 0.5e-3 + 1e-4 + 4e-4 = 1.0 ms; leaf 0 ready
    # 1 ms, link free 1.0 ms => fin 1.4 ms
    assert evs[-1][0] == pytest.approx(
        max(1e-3, 0.5e-3 + 1e-4 + 4e-4) + 4e-4)
    # dropped client streams its zero-delta leaves from round start
    evs, fin = sched.round_events(c, 2.0, 1, [4000, 4000], [0.5, 0.5],
                                  active=False)
    assert [k for _, k, _ in evs] == ["leaf_arrival", "leaf_arrival"]
    assert fin == pytest.approx(2.0 + 1e-4 + 8e-5)


def test_get_schedule_resolution():
    assert isinstance(get_schedule(None), BlockingSchedule)
    assert isinstance(get_schedule("blocking"), BlockingSchedule)
    assert isinstance(get_schedule("streaming"), StreamingSchedule)
    s = StreamingSchedule()
    assert get_schedule(s) is s
    with pytest.raises(ValueError, match="upload schedule"):
        get_schedule("bogus")


# ---------------------------------------------------------------------------
# Runtime: streaming is pure clock accounting
# ---------------------------------------------------------------------------

def test_streaming_bit_exact_trajectory_and_faster_clock(mlp_problem):
    loss_fn, eval_fn, p0, data = mlp_problem
    blk = runtime.run(loss_fn, p0, data, _stream_cfg(), eval_fn,
                      eval_every=8)
    stm = runtime.run(loss_fn, p0, data,
                      _stream_cfg(upload_schedule="streaming"), eval_fn,
                      eval_every=8)
    assert [(h.round, h.iteration, h.value) for h in blk.history] \
        == [(h.round, h.iteration, h.value) for h in stm.history]
    _tree_equal(blk.params, stm.params)
    # >= 4 leaves overlap: the modeled clock must strictly improve
    assert len(jax.tree.leaves(p0)) >= 4
    assert stm.wall_clock_s < blk.wall_clock_s
    # engine ledger (serial alpha-beta view) is schedule-independent
    assert stm.comm_bytes == blk.comm_bytes
    assert stm.comm_time_s == blk.comm_time_s


def test_streaming_single_leaf_cannot_overlap(golden_problem=None):
    """logreg has one leaf: its last local step releases the whole message
    at compute_done, so streaming and blocking clocks coincide exactly."""
    x, y = make_binary_classification(n=256, d=16, seed=3)
    data = {k: jnp.asarray(v)
            for k, v in partition_iid(x, y, 4, seed=0).items()}
    loss_fn = lambda p, b: logreg.loss_fn(p, b, 1e-2)
    eval_fn = lambda p: logreg.full_objective(p, jnp.asarray(x),
                                              jnp.asarray(y), 1e-2)
    p0 = logreg.init_params(None, 16)
    cfg = _stream_cfg(T1=8, n_stages=1, batch_per_client=8)
    blk = runtime.run(loss_fn, p0, data, cfg, eval_fn)
    stm = runtime.run(loss_fn, p0, data,
                      dataclasses.replace(cfg, upload_schedule="streaming"),
                      eval_fn)
    assert stm.wall_clock_s == pytest.approx(blk.wall_clock_s)


def test_streaming_rejects_async(mlp_problem):
    loss_fn, eval_fn, p0, data = mlp_problem
    with pytest.raises(ValueError, match="streaming"):
        runtime.run(loss_fn, p0, data,
                    _stream_cfg(algo="local", k1=4.0, async_mode=True,
                                upload_schedule="streaming"), eval_fn)


def test_streaming_dropout_deterministic(mlp_problem):
    """Dropped clients stream their zero-delta leaves; same seed =>
    identical trace, params, and leaf arrivals for every leaf."""
    loss_fn, eval_fn, p0, data = mlp_problem
    cfg = _stream_cfg(upload_schedule="streaming", dropout_rate=0.25,
                      T1=8, n_stages=1)
    runs = [runtime.run(loss_fn, p0, data, cfg, eval_fn, eval_every=4)
            for _ in range(2)]
    assert runs[0].trace == runs[1].trace
    _tree_equal(runs[0].params, runs[1].params)
    kinds = [e[1] for e in runs[0].trace]
    assert any(k == "dropout" for k in kinds)
    n_leaves = len(jax.tree.leaves(p0))
    # every client answers every round with all of its leaves, and every
    # per-leaf arrival stays attributable to its leaf index
    leaf_evs = [e for e in runs[0].trace if e[1] == "leaf_arrival"]
    assert len(leaf_evs) == 8 * n_leaves * kinds.count("merge")
    assert {e[3] for e in leaf_evs} == set(range(n_leaves))


# ---------------------------------------------------------------------------
# Per-leaf comm-ledger reconciliation
# ---------------------------------------------------------------------------

def test_legacy_reducer_without_leaf_bytes_still_runs_blocking():
    """A custom Reducer predating the per-leaf protocol (only reduce +
    message_bytes overridden) must keep working on blocking rounds — no
    leaf ledger — and be rejected with a clear error for streaming."""
    from repro.comm import Reducer
    from repro.utils.tree import tree_mean_leading as tml

    class LegacyMean(Reducer):
        name = "legacy"

        def reduce(self, stacked, state, rng):
            return tml(stacked), state

        def message_bytes(self, template):
            return sum(l.size * 4 for l in jax.tree.leaves(template))

    x, y = make_binary_classification(n=128, d=8, seed=0)
    data = {k: jnp.asarray(v)
            for k, v in partition_iid(x, y, 4, seed=0).items()}
    loss_fn = lambda p, b: logreg.loss_fn(p, b, 1e-2)
    eval_fn = lambda p: logreg.full_objective(p, jnp.asarray(x),
                                              jnp.asarray(y), 1e-2)
    p0 = logreg.init_params(None, 8)
    cfg = _stream_cfg(T1=4, n_stages=1, batch_per_client=8)
    res = runtime.run(loss_fn, p0, data, cfg, eval_fn, reducer=LegacyMean())
    assert res.rounds == 4
    assert res.leaf_ledger is None  # no per-leaf accounting available
    with pytest.raises(ValueError, match="leaf_message_bytes"):
        runtime.run(loss_fn, p0, data,
                    dataclasses.replace(cfg, upload_schedule="streaming"),
                    eval_fn, reducer=LegacyMean())


def test_leaf_message_bytes_sum_to_message_bytes(mlp_problem):
    _, _, p0, _ = mlp_problem
    for spec in ("dense", "int8", "int4", "topk", "staleness",
                 "staleness-int8"):
        red = get_reducer(spec)
        lb = red.leaf_message_bytes(p0)
        assert len(lb) == len(jax.tree.leaves(p0))
        assert sum(lb) == red.message_bytes(p0)


@pytest.mark.parametrize("reducer", ["dense", "int8"])
@pytest.mark.parametrize("topology", ["star", "hier"])
def test_leaf_ledger_reconciles_with_tree_totals(mlp_problem, reducer,
                                                 topology):
    """Streaming per-leaf totals (bytes and modeled seconds, summed over
    leaves and hops) equal the blocking tree-level engine ledger — dense
    and int8, flat star and hierarchical."""
    loss_fn, eval_fn, p0, data = mlp_problem
    kw = dict(reducer=reducer, topology=topology, n_pods=2,
              T1=8, n_stages=1)
    blk = runtime.run(loss_fn, p0, data, _stream_cfg(**kw), eval_fn,
                      eval_every=4)
    stm = runtime.run(
        loss_fn, p0, data,
        _stream_cfg(upload_schedule="streaming", **kw), eval_fn,
        eval_every=4)
    assert stm.leaf_ledger, "streaming run must carry the per-leaf ledger"
    n_hops = 2 if topology == "hier" else 1
    assert len(stm.leaf_ledger) == n_hops * len(jax.tree.leaves(p0))
    # bytes reconcile bit-exactly (integer per-leaf formulas)
    assert sum(l["bytes"] for l in stm.leaf_ledger) == blk.comm_bytes
    # modeled seconds reconcile to float-sum precision
    t = math.fsum(l["time_s"] for l in stm.leaf_ledger)
    assert t == pytest.approx(blk.comm_time_s, rel=1e-12)
    # and the trajectory is reducer/topology-faithful but schedule-free
    assert [(h.round, h.value) for h in blk.history] \
        == [(h.round, h.value) for h in stm.history]


# ---------------------------------------------------------------------------
# StreamingStar topology (execution half)
# ---------------------------------------------------------------------------

def test_streaming_star_bit_exact_with_star():
    rng = jax.random.key(0)
    stacked = {"a": jax.random.normal(rng, (4, 33)),
               "b": {"c": jax.random.normal(jax.random.fold_in(rng, 1),
                                            (4, 5, 7)),
                     "d": jax.random.normal(jax.random.fold_in(rng, 2),
                                            (4, 11))}}
    for spec in ("dense", "int8", "topk"):
        star = Star(reducer=get_reducer(spec))
        stream = StreamingStar(reducer=get_reducer(spec))
        c1, s1 = star.reduce(stacked, star.init_state(stacked),
                             jax.random.key(7))
        c2, s2 = stream.reduce(stacked, stream.init_state(stacked),
                               jax.random.key(7))
        _tree_equal(c1, c2)
        _tree_equal(s1, s2)
        # inherited cost model: streaming and blocking ledgers reconcile
        assert stream.round_bytes(stacked, 4) == star.round_bytes(stacked, 4)
        lc = stream.leaf_costs(stacked, 4)
        assert sum(l.bytes for l in lc) == stream.round_bytes(stacked, 4)
        assert math.fsum(l.time_s for l in lc) \
            == pytest.approx(stream.round_time(stacked, 4), rel=1e-12)
    assert isinstance(get_topology("streaming"), StreamingStar)
    assert get_topology("streaming").name == "streaming-star"


def test_leaf_costs_reconcile_with_downlink_billed():
    """count_downlink links bill the dense broadcast too; the per-leaf
    ledger must mirror round_bytes or streaming runs under-report."""
    tmpl = {"a": jnp.zeros((33,)), "b": jnp.zeros((5, 7))}
    net = NetworkModel(latency_s=1e-3, bandwidth_gbps=1.0,
                       count_downlink=True)
    for spec in ("dense", "int8"):
        topo = StreamingStar(reducer=get_reducer(spec), network=net)
        lc = topo.leaf_costs(tmpl, 4)
        assert sum(l.bytes for l in lc) == topo.round_bytes(tmpl, 4)
        assert math.fsum(l.time_s for l in lc) \
            == pytest.approx(topo.round_time(tmpl, 4), rel=1e-12)


def test_simulator_streaming_topology_matches_star(mlp_problem):
    loss_fn, eval_fn, p0, data = mlp_problem
    cfg = _stream_cfg(algo="stl_sc", T1=8, k1=2.0, n_stages=2,
                      reducer="int8")
    h_star = simulate.run(loss_fn, p0, data, cfg, eval_fn, topology="star")
    h_stream = simulate.run(loss_fn, p0, data, cfg, eval_fn,
                            topology="streaming")
    assert [(h.round, h.value) for h in h_star] \
        == [(h.round, h.value) for h in h_stream]


# ---------------------------------------------------------------------------
# build_sync_step(streaming=True) + StagewiseDriver
# ---------------------------------------------------------------------------

def _driver_state(n=4, d=16, seed=0):
    key = jax.random.key(seed)
    params = {"w1": jax.random.normal(key, (d, d)),
              "w2": jax.random.normal(jax.random.fold_in(key, 1), (d,))}
    return {"params": tree_broadcast_leading(params, n),
            "opt": {"mu": jax.tree.map(jnp.zeros_like,
                                       tree_broadcast_leading(params, n))},
            "step": jnp.zeros((), jnp.int32)}


def _perturb(state, seed=9):
    key = jax.random.key(seed)
    params = jax.tree.map(
        lambda x: x + 0.01 * jax.random.normal(
            jax.random.fold_in(key, x.shape[-1]), x.shape),
        state["params"])
    return dict(state, params=params)


@pytest.mark.parametrize("reducer", [None, "int8"])
def test_build_sync_step_streaming_bit_exact(reducer):
    state = _perturb(_driver_state())
    blocking = jax.jit(LS.build_sync_step(reducer))
    streaming = jax.jit(LS.build_sync_step(reducer, streaming=True))
    out_b, out_s = blocking(state), streaming(state)
    assert set(out_b.keys()) == set(out_s.keys())  # same state contract
    _tree_equal(out_b["params"], out_s["params"])
    if reducer is not None:
        _tree_equal(out_b["comm"], out_s["comm"])
        # second round threads the comm state identically
        _tree_equal(blocking(out_b)["params"], streaming(out_s)["params"])


def test_driver_accepts_streaming_topology_and_carries_leaf_ledger():
    from repro.core.stl_sgd import StagewiseDriver

    d = 16

    def toy_loss(params, batch, eta):  # pragma: no cover - signature only
        raise NotImplementedError

    def train_step(state, batch, eta):
        g = jax.tree.map(lambda x: 0.01 * x, state["params"])
        return dict(state, params=jax.tree.map(jnp.subtract,
                                               state["params"], g),
                    step=state["step"] + 1), {"loss": jnp.zeros(())}

    sync_step = LS.build_sync_step("int8", streaming=True)
    tcfg = TrainConfig(algo="local", T1=8, k1=2.0, n_stages=1,
                       topology="streaming")
    drv = StagewiseDriver(tcfg, train_step, sync_step)
    assert drv.topology.streaming
    assert drv.topology.reducer.name == "int8"
    batches = iter([{"x": None}] * 64)
    ds = drv.run(_perturb(_driver_state(d=d)), batches)
    assert ds.rounds_total == 4
    assert ds.leaf_ledger, "streaming driver must carry the per-leaf ledger"
    assert sum(l["bytes"] for l in ds.leaf_ledger) == ds.comm_bytes_total
    assert math.fsum(l["time_s"] for l in ds.leaf_ledger) \
        == pytest.approx(ds.comm_time_s, rel=1e-12)
    # a streaming sync_step runs (and is priced as) the per-leaf round
    # even under a plain "star" config
    drv2 = StagewiseDriver(TrainConfig(algo="local", T1=4, k1=2.0,
                                       n_stages=1), train_step, sync_step)
    assert drv2.topology.streaming
    # a hierarchical config still refuses a *flat* sync step (streaming or
    # not): the ledger would price an inter-pod hop the round never crosses
    with pytest.raises(ValueError, match="build_sync_step"):
        StagewiseDriver(TrainConfig(algo="local", topology="hier"),
                        train_step, sync_step)
    with pytest.raises(ValueError, match="build_sync_step"):
        StagewiseDriver(TrainConfig(algo="local", topology="streaming-hier"),
                        train_step, sync_step)
