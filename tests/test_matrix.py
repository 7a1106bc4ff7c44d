"""The topology × schedule × reducer conformance matrix.

Every cell of {star, streaming-star, hier, streaming-hier} ×
{blocking, streaming} × {dense, int8, topk} is exercised on all three
execution surfaces — the vmapped simulator (pure numerics), the
StagewiseDriver (executed collectives + priced ledger), and the event
runtime (numerics + modeled clock) — with downlink billing on.  No
cell is refused.  Supported-cell invariants:

  * the schedule axis is pure clock accounting: blocking and streaming
    schedules produce bit-identical params and (round, objective)
    histories, and the streaming clock never loses;
  * the topology streaming variants are pure scheduling too:
    StreamingStar ≡ Star and Hierarchical(streaming=True) ≡
    Hierarchical bit-exactly, error-feedback state included;
  * the dense column collapses: every topology degenerates to the flat
    star mean bit-exactly;
  * the per-(leaf, hop) ledger — uplink, intra/inter-pod, downlink —
    reconciles with the tree-level totals in every cell (bytes
    bit-exactly, modeled seconds to float-sum precision).

Combinations outside the matrix stay refused with actionable error
text, pinned here: asynchronous merging × {streaming schedules,
non-star topologies, downlink billing}, per-leaf schedules over
reducers without per-leaf payload accounting, and flat sync steps
under hierarchical driver configs.  The capability probe
(``supports_leaf_bytes``) is a regression target of its own: an
*implemented but raising* ``leaf_message_bytes`` must propagate, never
silently degrade to monolithic blob pricing.
"""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import runtime
from repro.comm import (
    DenseMean,
    NetworkModel,
    Reducer,
    get_reducer,
    supports_leaf_bytes,
)
from repro.configs.base import TrainConfig
from repro.core import local_sgd as LS
from repro.core import simulate
from repro.core.stl_sgd import StagewiseDriver
from repro.data import make_binary_classification, partition_iid
from repro.engine import Hierarchical, Star, StreamingStar, get_topology
from repro.models import mlp
from repro.runtime import BlockingSchedule, ClientProcess, StreamingSchedule
from repro.runtime.schedule import get_schedule
from repro.utils.tree import tree_broadcast_leading, tree_mean_leading

REDUCERS = ["dense", "int8", "topk"]
TOPOLOGIES = ["star", "streaming", "hier", "streaming-hier"]
SCHEDULES = ["blocking", "streaming"]
N_CLIENTS, N_PODS = 4, 2


def _tree_equal(a, b):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def _hist(res):
    return [(h.round, h.iteration, h.value) for h in res.history]


# ---------------------------------------------------------------------------
# Event-runtime cells (lazy, cached across tests in this module)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def problem():
    x, y = make_binary_classification(n=256, d=32, seed=0)
    lam = 1e-3
    data = {k: jnp.asarray(v)
            for k, v in partition_iid(x, y, N_CLIENTS, seed=1).items()}
    xj, yj = jnp.asarray(x), jnp.asarray(y)
    loss_fn = lambda p, b: mlp.loss_fn(p, b, lam)
    eval_fn = jax.jit(lambda p: mlp.full_objective(p, xj, yj, lam))
    return loss_fn, eval_fn, mlp.init_params(jax.random.key(7), 32), data


def _cell_cfg(topology, schedule, reducer, **kw):
    base = dict(algo="local", eta1=0.1, T1=8, k1=2.0, n_stages=1,
                batch_per_client=8, seed=0,
                reducer=reducer, inter_reducer=reducer,
                topology=topology, n_pods=N_PODS,
                upload_schedule=schedule, count_downlink=True,
                comm_latency_s=1e-4, comm_bandwidth_gbps=0.45,
                base_step_time_s=1e-3,
                straggler_frac=0.25, straggler_slowdown=2.0)
    base.update(kw)
    return TrainConfig(**base)


_RUNS = {}


def _run(problem, topology, schedule, reducer):
    key = (topology, schedule, reducer)
    if key not in _RUNS:
        loss_fn, eval_fn, p0, data = problem
        _RUNS[key] = runtime.run(
            loss_fn, p0, data, _cell_cfg(topology, schedule, reducer),
            eval_fn, eval_every=2)
    return _RUNS[key]


@pytest.mark.parametrize("reducer", REDUCERS)
def test_matrix_event_backend(problem, reducer):
    runs = {(t, s): _run(problem, t, s, reducer)
            for t in TOPOLOGIES for s in SCHEDULES}
    # no cell refused, every cell ran its full round budget on the clock
    for r in runs.values():
        assert r.rounds == 4 and r.wall_clock_s > 0.0

    # schedule axis is pure clock: identical numerics, clock never loses
    for t in TOPOLOGIES:
        blk, stm = runs[(t, "blocking")], runs[(t, "streaming")]
        assert _hist(blk) == _hist(stm)
        _tree_equal(blk.params, stm.params)
        assert stm.wall_clock_s <= blk.wall_clock_s
        # the engine ledger (serial α–β view) is schedule-independent
        assert stm.comm_bytes == blk.comm_bytes
        assert stm.comm_time_s == blk.comm_time_s

    # topology streaming variants are pure scheduling: bit-exact numerics
    for base, stream in (("star", "streaming"), ("hier", "streaming-hier")):
        for s in SCHEDULES:
            assert _hist(runs[(base, s)]) == _hist(runs[(stream, s)])
            _tree_equal(runs[(base, s)].params, runs[(stream, s)].params)

    # dense column: every topology collapses to the flat star mean
    if reducer == "dense":
        ref = runs[("star", "blocking")]
        for cell, r in runs.items():
            assert _hist(r) == _hist(ref), cell
            _tree_equal(r.params, ref.params)

    # per-(leaf, hop) ledger reconciles in every cell, downlink included
    n_leaves = len(jax.tree.leaves(problem[2]))
    for (t, s), r in runs.items():
        assert r.leaf_ledger, (t, s)
        hops = {l["hop"] for l in r.leaf_ledger}
        if t in ("star", "streaming"):
            assert hops == {"uplink", "downlink"}
            assert len(r.leaf_ledger) == 2 * n_leaves
        else:
            assert hops == {"intra_pod", "inter_pod", "downlink"}
            assert len(r.leaf_ledger) == 3 * n_leaves
        assert sum(l["bytes"] for l in r.leaf_ledger) == r.comm_bytes
        assert math.fsum(l["time_s"] for l in r.leaf_ledger) \
            == pytest.approx(r.comm_time_s, rel=1e-12)

    # ≥ 4 leaves overlap under 2× stragglers: the flat streaming cell must
    # strictly beat blocking, not just tie
    assert n_leaves >= 4
    assert runs[("star", "streaming")].wall_clock_s \
        < runs[("star", "blocking")].wall_clock_s


def test_wan_streaming_compounds_the_overlap(problem):
    """streaming∘hierarchical: streaming only the uplink (the PR-4
    comparator) already beats blocking; streaming the WAN hop and the
    downlink too compounds the win — all three bit-exact in params."""
    loss_fn, eval_fn, p0, data = problem
    blk = _run(problem, "streaming-hier", "blocking", "int8")
    full = _run(problem, "streaming-hier", "streaming", "int8")
    up = runtime.run(
        loss_fn, p0, data,
        _cell_cfg("streaming-hier", "streaming-uplink", "int8"),
        eval_fn, eval_every=2)
    assert _hist(blk) == _hist(up) == _hist(full)
    _tree_equal(blk.params, up.params)
    _tree_equal(blk.params, full.params)
    assert up.wall_clock_s < blk.wall_clock_s
    assert full.wall_clock_s < up.wall_clock_s


@pytest.mark.parametrize("reducer", REDUCERS)
@pytest.mark.parametrize("topology", TOPOLOGIES)
def test_matrix_simulator_agrees_with_event_backend(problem, topology,
                                                    reducer):
    """The vmapped simulator runs every topology cell and lands on the
    event backend's trajectory exactly (heterogeneity is pure clock)."""
    loss_fn, eval_fn, p0, data = problem
    h_sim = simulate.run(loss_fn, p0, data,
                         _cell_cfg(topology, "blocking", reducer),
                         eval_fn, eval_every=2)
    got = [(h.round, h.iteration, h.value) for h in h_sim]
    assert got == _hist(_run(problem, topology, "blocking", reducer))


# ---------------------------------------------------------------------------
# Topology.reduce cells: consensus AND reducer state bit-exact
# ---------------------------------------------------------------------------

def _stacked(seed=0):
    key = jax.random.key(seed)
    return {"a": jax.random.normal(key, (N_CLIENTS, 17)),
            "b": {"c": jax.random.normal(jax.random.fold_in(key, 1),
                                         (N_CLIENTS, 3, 5)),
                  "d": jax.random.normal(jax.random.fold_in(key, 2),
                                         (N_CLIENTS, 9))}}


@pytest.mark.parametrize("reducer", REDUCERS)
def test_matrix_topology_reduce_bit_exact(reducer):
    """Two evolving rounds through each topology: the streaming variants
    match their blocking bases bit-exactly, error-feedback state
    included; the dense column collapses to the flat mean."""
    topos = {
        "star": Star(reducer=get_reducer(reducer)),
        "streaming": StreamingStar(reducer=get_reducer(reducer)),
        "hier": Hierarchical(n_pods=N_PODS, intra=get_reducer(reducer),
                             inter=get_reducer(reducer)),
        "streaming-hier": Hierarchical(n_pods=N_PODS,
                                       intra=get_reducer(reducer),
                                       inter=get_reducer(reducer),
                                       streaming=True),
    }
    stacked = _stacked()
    states = {k: t.init_state(stacked) for k, t in topos.items()}
    outs = {}
    for rnd in range(2):
        rng = jax.random.fold_in(jax.random.key(3), rnd)
        for k, t in topos.items():
            outs[k], states[k] = t.reduce(stacked, states[k], rng)
        # evolve the replicas so round 2 exercises threaded EF state
        stacked = jax.tree.map(lambda x: 0.9 * x, stacked)
        for base, stream in (("star", "streaming"),
                             ("hier", "streaming-hier")):
            _tree_equal(outs[base], outs[stream])
            _tree_equal(states[base], states[stream])
        if reducer == "dense":
            for k in topos:
                _tree_equal(outs[k], outs["star"])


# ---------------------------------------------------------------------------
# StagewiseDriver cells: executed collectives + priced ledger
# ---------------------------------------------------------------------------

def _driver_state(n=N_CLIENTS, d=12, seed=0):
    key = jax.random.key(seed)
    params = {"w1": jax.random.normal(key, (d, d)),
              "w2": jax.random.normal(jax.random.fold_in(key, 1), (d,))}
    state = {"params": tree_broadcast_leading(params, n),
             "opt": {"mu": jax.tree.map(
                 jnp.zeros_like, tree_broadcast_leading(params, n))},
             "step": jnp.zeros((), jnp.int32)}
    state["params"] = jax.tree.map(
        lambda x: x + 0.01 * jax.random.normal(
            jax.random.fold_in(key, x.shape[-1]), x.shape),
        state["params"])
    return state


def _toy_train_step(state, batch, eta):
    params = jax.tree.map(lambda x: x * (1.0 - 0.01 * eta), state["params"])
    return dict(state, params=params, step=state["step"] + 1), \
        {"loss": jnp.zeros(())}


def _driver_cell(topology, reducer):
    red = None if reducer == "dense" else reducer
    hier = topology in ("hier", "streaming-hier")
    streaming = topology in ("streaming", "streaming-hier")
    sync = LS.build_sync_step(red, streaming=streaming, hierarchical=hier,
                              n_pods=N_PODS, inter_reducer=red or "dense")
    tcfg = TrainConfig(algo="local", T1=8, k1=2.0, n_stages=1,
                       reducer=reducer, inter_reducer=reducer,
                       topology=topology, n_pods=N_PODS,
                       count_downlink=True)
    drv = StagewiseDriver(tcfg, _toy_train_step, sync)
    assert drv.topology.streaming == streaming
    assert isinstance(drv.topology, Hierarchical) == hier
    return drv.run(_driver_state(), iter([None] * 64))


@pytest.mark.parametrize("reducer", REDUCERS)
def test_matrix_driver(reducer):
    runs = {t: _driver_cell(t, reducer) for t in TOPOLOGIES}
    for ds in runs.values():
        assert ds.rounds_total == 4
    # streaming variants execute the identical round (params + EF state)
    for base, stream in (("star", "streaming"), ("hier", "streaming-hier")):
        _tree_equal(runs[base].state["params"], runs[stream].state["params"])
        if reducer != "dense":
            _tree_equal(runs[base].state["comm"], runs[stream].state["comm"])
        assert runs[base].comm_bytes_total == runs[stream].comm_bytes_total
    # dense column collapses to the flat star round
    if reducer == "dense":
        for t in TOPOLOGIES:
            _tree_equal(runs[t].state["params"], runs["star"].state["params"])
    # the priced per-(leaf, hop) ledger reconciles, downlink included
    for t, ds in runs.items():
        hops = {l["hop"] for l in ds.leaf_ledger}
        if t in ("star", "streaming"):
            assert hops == {"uplink", "downlink"}
        else:
            assert hops == {"intra_pod", "inter_pod", "downlink"}
        assert sum(l["bytes"] for l in ds.leaf_ledger) == ds.comm_bytes_total
        assert math.fsum(l["time_s"] for l in ds.leaf_ledger) \
            == pytest.approx(ds.comm_time_s, rel=1e-12)


# ---------------------------------------------------------------------------
# Downlink schedule arithmetic (fixed examples; hypothesis versions of the
# tiling/partition laws live in tests/test_property.py)
# ---------------------------------------------------------------------------

def _client(count_downlink, alpha=1e-4, gbps=0.8):
    return ClientProcess(cid=0, rate=1.0, step_time_s=1e-3,
                         network=NetworkModel(latency_s=alpha,
                                              bandwidth_gbps=gbps,
                                              count_downlink=count_downlink))


def test_blocking_broadcast_events():
    # unbilled downlink: the consensus lands free and instantly at merge
    evs, ready = BlockingSchedule().broadcast_events(
        _client(False), [1.0e-3, 2.0e-3], [4000, 4000])
    assert evs == [] and ready == 2.0e-3
    # billed: one monolithic broadcast after the merge, α + Σbytes/β
    evs, ready = BlockingSchedule().broadcast_events(
        _client(True), [1.0e-3, 2.0e-3], [4000, 4000])
    assert [k for _, k, _ in evs] == ["broadcast_arrival"]
    assert ready == pytest.approx(2.0e-3 + 1e-4 + 8000 / 1e8)
    assert evs[0][0] == ready


def test_streaming_broadcast_reverse_order_and_link_queue():
    """The downlink mirrors the uplink: leaf l's broadcast starts as soon
    as the server finishes reducing it (reverse-leaf order), α once, one
    serial link — so the client is ready before the blocking monolith."""
    c = _client(True)  # α 0.1 ms, 1e8 B/s
    leaf_done = [2.0e-3, 1.5e-3]  # the server reduced leaf 1 first
    evs, ready = StreamingSchedule().broadcast_events(
        c, leaf_done, [4000, 4000])
    assert [k for _, k, _ in evs] == ["leaf_broadcast", "leaf_broadcast"]
    assert [info for _, _, info in evs] == [(1,), (0,)]
    # leaf 1: 1.5 ms + α + 4000/1e8 = 1.64 ms
    assert evs[0][0] == pytest.approx(1.5e-3 + 1e-4 + 4e-5)
    # leaf 0: ready at 2.0 ms, link free at 1.64 ms -> 2.04 ms
    assert evs[1][0] == pytest.approx(2.0e-3 + 4e-5)
    assert ready == evs[1][0]
    _, ready_blk = BlockingSchedule().broadcast_events(c, leaf_done,
                                                       [4000, 4000])
    assert ready < ready_blk
    # link-bound regime: broadcasts queue back-to-back behind the stream
    evs, ready = StreamingSchedule().broadcast_events(
        c, [1.0e-3, 0.5e-3], [40000, 40000])
    assert evs[0][0] == pytest.approx(0.5e-3 + 1e-4 + 4e-4)
    assert ready == pytest.approx(evs[0][0] + 4e-4)
    # unbilled: streaming falls back to the free instant broadcast too
    evs, ready = StreamingSchedule().broadcast_events(
        _client(False), leaf_done, [4000, 4000])
    assert evs == [] and ready == 2.0e-3


def test_streaming_uplink_only_is_the_uplink_comparator():
    """StreamingSchedule(uplink_only=True) streams the uplink but keeps
    the monolithic broadcast and the serial WAN barrier — the PR-4
    behavior, kept addressable as an ablation comparator."""
    up = get_schedule("streaming-uplink")
    assert isinstance(up, StreamingSchedule) and up.uplink_only
    assert up.name == "streaming-uplink"
    assert up.streams_uplink and not up.streams_round
    full = get_schedule("streaming")
    assert full.name == "streaming"
    assert full.streams_uplink and full.streams_round
    blk = get_schedule("blocking")
    assert not blk.streams_uplink and not blk.streams_round
    # uplink-only broadcasts exactly like the blocking schedule
    c = _client(True)
    assert up.broadcast_events(c, [1.0e-3, 2.0e-3], [4000, 4000]) \
        == BlockingSchedule().broadcast_events(c, [1.0e-3, 2.0e-3],
                                               [4000, 4000])


# ---------------------------------------------------------------------------
# Capability probe: implemented-but-raising must propagate
# ---------------------------------------------------------------------------

class _LegacyMean(Reducer):
    """Pre-per-leaf-protocol reducer: only reduce/message_bytes."""
    name = "legacy"

    def reduce(self, stacked, state, rng):
        return tree_mean_leading(stacked), state

    def message_bytes(self, template):
        return sum(l.size * 4 for l in jax.tree.leaves(template))


class _BrokenLeafMean(DenseMean):
    """Per-leaf protocol *implemented* but buggy: the probe must route
    callers into the method and let the failure propagate — the old
    ``except NotImplementedError`` fallbacks silently re-priced the run
    as one monolithic blob instead."""
    name = "broken-leaf"

    def leaf_message_bytes(self, template):
        raise NotImplementedError("per-leaf accounting bug")


def test_supports_leaf_bytes_probe():
    assert not supports_leaf_bytes(_LegacyMean())
    assert supports_leaf_bytes(DenseMean())
    for spec in REDUCERS:
        assert supports_leaf_bytes(get_reducer(spec))
    # overriding counts as support even when the override raises
    assert supports_leaf_bytes(_BrokenLeafMean())


def test_raising_leaf_bytes_propagates_not_degrades():
    tmpl = {"a": jnp.zeros((8,)), "b": jnp.zeros((3, 5))}
    with pytest.raises(NotImplementedError, match="accounting bug"):
        Star(reducer=_BrokenLeafMean()).leaf_costs(tmpl, N_CLIENTS)
    with pytest.raises(NotImplementedError, match="accounting bug"):
        Hierarchical(n_pods=N_PODS, intra=_BrokenLeafMean(),
                     inter=get_reducer("int8")).leaf_costs(tmpl, N_CLIENTS)
    with pytest.raises(NotImplementedError, match="accounting bug"):
        Hierarchical(n_pods=N_PODS, intra=DenseMean(),
                     inter=_BrokenLeafMean()).leaf_costs(tmpl, N_CLIENTS)
    # the legacy (genuinely unimplemented) reducer still degrades cleanly:
    # no per-leaf rows, tree-level pricing only
    assert Star(reducer=_LegacyMean()).leaf_costs(tmpl, N_CLIENTS) == []


def test_runtime_raising_leaf_bytes_propagates(problem):
    loss_fn, eval_fn, p0, data = problem
    cfg = _cell_cfg("star", "blocking", "dense")
    with pytest.raises(NotImplementedError, match="accounting bug"):
        runtime.run(loss_fn, p0, data, cfg, eval_fn,
                    reducer=_BrokenLeafMean())


# ---------------------------------------------------------------------------
# Unsupported cells: pinned, actionable refusals
# ---------------------------------------------------------------------------

def test_refusal_async_streaming_schedule(problem):
    loss_fn, eval_fn, p0, data = problem
    cfg = _cell_cfg("star", "streaming", "dense", async_mode=True,
                    count_downlink=False)
    with pytest.raises(ValueError, match="streaming.*synchronous policy"):
        runtime.run(loss_fn, p0, data, cfg, eval_fn)


def test_refusal_async_non_star_topology(problem):
    loss_fn, eval_fn, p0, data = problem
    for topo in ("hier", "streaming", "streaming-hier"):
        cfg = _cell_cfg(topo, "blocking", "dense", async_mode=True,
                        count_downlink=False)
        with pytest.raises(ValueError, match="flat star protocol"):
            runtime.run(loss_fn, p0, data, cfg, eval_fn)


def test_refusal_async_count_downlink(problem):
    loss_fn, eval_fn, p0, data = problem
    cfg = _cell_cfg("star", "blocking", "dense", async_mode=True)
    with pytest.raises(ValueError, match="barrier rounds only"):
        runtime.run(loss_fn, p0, data, cfg, eval_fn)


def test_refusal_legacy_reducer_streaming(problem):
    loss_fn, eval_fn, p0, data = problem
    cfg = _cell_cfg("star", "streaming", "dense", count_downlink=False)
    with pytest.raises(ValueError, match="leaf_message_bytes"):
        runtime.run(loss_fn, p0, data, cfg, eval_fn, reducer=_LegacyMean())


def test_refusal_flat_step_under_hier_config():
    flat = LS.build_sync_step(None, streaming=True)
    for topo in ("hier", "streaming-hier"):
        with pytest.raises(ValueError, match="build_sync_step"):
            StagewiseDriver(
                TrainConfig(algo="local", topology=topo, n_pods=N_PODS),
                _toy_train_step, flat)


def test_refusal_unknown_specs():
    with pytest.raises(ValueError, match="unknown topology spec"):
        get_topology("bogus")
    with pytest.raises(ValueError, match="upload schedule"):
        get_schedule("bogus")
