"""repro.runtime: event clock, heterogeneous clients, async merging.

The decisive invariants:
  * with heterogeneity disabled, EventBackend's synchronous path is
    bit-exact with the vmapped simulator — pinned against the same
    golden stl_sc trace as tests/test_engine.py (tests/goldens.py), and
    bitwise-equal to ``simulate.run`` for EveryStep/FixedPeriod;
  * the clock is pure accounting: stragglers stretch modeled wall-clock
    without touching the trajectory; barrier rounds are priced at the
    slowest active client;
  * dropout is deterministic: same seed ⇒ identical event trace and final
    params, including hierarchical topology + error feedback;
  * AsyncPeriod is work-conserving: under stragglers it beats the
    synchronous schedule on modeled wall-clock at ~unchanged objective,
    and its StalenessWeightedMean merge is EF-compatible at int8;
  * AdaptivePeriod's divergence trigger interpolates between EveryStep
    (threshold 0) and the k-cap (threshold ∞).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import goldens as G
from repro.comm import NetworkModel, StalenessWeightedMean, get_reducer
from repro.configs.base import TrainConfig
from repro.core import simulate
from repro import runtime
from repro.engine import (
    AdaptivePeriod,
    Algorithm,
    AsyncPeriod,
    Engine,
    FixedPeriod,
    StagewiseGeometric,
    get_algorithm,
    make_async,
)
from repro.runtime import (
    Clock,
    EventBackend,
    EventQueue,
    Heterogeneity,
    sample_clients,
)

@pytest.fixture(scope="module")
def golden_problem():
    return G.golden_problem()


def _golden_cfg(**kw):
    return TrainConfig(**{**G.STL_SC_CFG, **kw})


# ---------------------------------------------------------------------------
# Clock / client sampling primitives
# ---------------------------------------------------------------------------

def test_event_queue_orders_by_time_then_insertion():
    q = EventQueue()
    q.push(2.0, "b", 1)
    q.push(1.0, "a", 0)
    q.push(1.0, "c", 2)   # same time as "a": FIFO tie-break
    got = [(q.pop().kind) for _ in range(3)]
    assert got == ["a", "c", "b"]
    clock = Clock()
    assert clock.advance(1.5) == 1.5
    assert clock.advance(1.0) == 1.5  # time never flows backwards


def test_sample_clients_deterministic_and_stragglers():
    het = Heterogeneity(base_step_time_s=1e-3, straggler_frac=0.25,
                        straggler_slowdown=4.0, jitter=0.1, seed=7)
    a = sample_clients(8, het)
    b = sample_clients(8, het)
    assert a == b  # pure function of (n, profile)
    assert sum(c.straggler for c in a) == 2
    strag = [c for c in a if c.straggler]
    rest = [c for c in a if not c.straggler]
    assert min(c.step_time_s for c in strag) > max(c.step_time_s
                                                   for c in rest)
    # jitter actually varies the cohort
    assert len({c.rate for c in rest}) > 1
    # homogeneous profile: all identical, nominal rate
    hom = sample_clients(4, Heterogeneity())
    assert not Heterogeneity().enabled
    assert all(c.rate == 1.0 and c.step_time_s == 1e-3 for c in hom)


# ---------------------------------------------------------------------------
# Bit-exactness: EventBackend == vmapped simulator when homogeneous
# ---------------------------------------------------------------------------

def test_event_backend_stl_sc_bit_exact_with_golden_trace(golden_problem):
    loss_fn, eval_fn, p0, data = golden_problem
    res = runtime.run(loss_fn, p0, data, _golden_cfg(), eval_fn,
                      eval_every=1)
    assert G.trace(res.history) == G.GOLDEN_STL_SC
    # and the clock priced 32 homogeneous barrier rounds
    assert res.rounds == 32
    assert res.wall_clock_s > 0.0


@pytest.mark.parametrize("algo,kw", [
    ("sync", dict(T1=24, k1=1.0, n_stages=2)),       # EveryStep
    ("local", dict(T1=24, k1=4.0, n_stages=2)),      # FixedPeriod
    ("stl_sc", dict(T1=12, k1=2.0, n_stages=3)),     # StagewiseGeometric
])
def test_event_backend_matches_simulator_bitwise(golden_problem, algo, kw):
    loss_fn, eval_fn, p0, data = golden_problem
    cfg = _golden_cfg(algo=algo, **kw)
    h_sim = simulate.run(loss_fn, p0, data, cfg, eval_fn, eval_every=2)
    res = runtime.run(loss_fn, p0, data, cfg, eval_fn, eval_every=2)
    assert [(h.round, h.iteration, h.value) for h in h_sim] \
        == [(h.round, h.iteration, h.value) for h in res.history]


def test_stragglers_stretch_clock_not_trajectory(golden_problem):
    """Stragglers are pure clock: the barrier keeps numerics identical while
    every round is priced at the slowest client."""
    loss_fn, eval_fn, p0, data = golden_problem
    base = runtime.run(loss_fn, p0, data, _golden_cfg(), eval_fn,
                       eval_every=1)
    slow = runtime.run(
        loss_fn, p0, data,
        _golden_cfg(straggler_frac=0.25, straggler_slowdown=4.0),
        eval_fn, eval_every=1)
    assert [(h.round, h.value) for h in base.history] \
        == [(h.round, h.value) for h in slow.history]
    assert slow.wall_clock_s > 2.0 * base.wall_clock_s
    # per-round cost = k·(slowest step time) + slowest upload (+ α)
    het = Heterogeneity(straggler_frac=0.25, straggler_slowdown=4.0, seed=0)
    clients = sample_clients(4, het, NetworkModel())
    msg = get_reducer("dense").message_bytes(p0)
    k1_round = 2 * max(c.step_time_s for c in clients) \
        + max(c.upload_time(msg) for c in clients)
    assert slow.timeline[1][0] == pytest.approx(k1_round)


# ---------------------------------------------------------------------------
# Dropout determinism (sync masked path + hierarchical topology + EF)
# ---------------------------------------------------------------------------

def _dropout_cfg(**kw):
    return _golden_cfg(dropout_rate=0.25, straggler_frac=0.25,
                       straggler_slowdown=2.0, **kw)


def test_dropout_same_seed_identical_trace_and_params(golden_problem):
    loss_fn, eval_fn, p0, data = golden_problem
    runs = [runtime.run(loss_fn, p0, data, _dropout_cfg(), eval_fn,
                        eval_every=2) for _ in range(2)]
    assert runs[0].trace == runs[1].trace
    assert len(runs[0].trace) > 0
    for a, b in zip(jax.tree.leaves(runs[0].params),
                    jax.tree.leaves(runs[1].params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert [(h.round, h.value) for h in runs[0].history] \
        == [(h.round, h.value) for h in runs[1].history]
    # dropout actually bites: trajectory differs from full participation
    full = runtime.run(loss_fn, p0, data, _golden_cfg(), eval_fn,
                       eval_every=2)
    assert [h.value for h in full.history] \
        != [h.value for h in runs[0].history]
    assert any(e[1] == "dropout" for e in runs[0].trace)
    # a dropped client still answers the barrier with its zero-delta
    # message (matching the masked numerics): every round sees N arrivals
    kinds = [e[1] for e in runs[0].trace]
    assert kinds.count("arrival") == 4 * kinds.count("merge")
    assert kinds.count("compute_done") \
        == 4 * kinds.count("merge") - kinds.count("dropout")


def test_dropout_hierarchical_ef_deterministic_and_converges(golden_problem):
    """Dropped clients contribute a zero delta, so the hierarchical
    dense-ICI + int8-EF-WAN topology composes with partial participation:
    same seed reproduces the run exactly, and the objective still lands
    near the flat-dense run."""
    loss_fn, eval_fn, p0, data = golden_problem
    cfg = _dropout_cfg(topology="hier", n_pods=2, inter_reducer="int8")
    runs = [runtime.run(loss_fn, p0, data, cfg, eval_fn, eval_every=4)
            for _ in range(2)]
    assert runs[0].trace == runs[1].trace
    for a, b in zip(jax.tree.leaves(runs[0].params),
                    jax.tree.leaves(runs[1].params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    flat = runtime.run(loss_fn, p0, data, _golden_cfg(), eval_fn,
                       eval_every=4)
    assert abs(runs[0].history[-1].value - flat.history[-1].value) < 2e-2
    # the inter-pod hop is priced on every replayed round
    assert runs[0].wall_clock_s > 0.0
    assert any(e[1] == "merge" for e in runs[0].trace)


def test_async_dropout_same_seed_identical(golden_problem):
    """momentum > 0 also exercises the drop path's optimizer-state restore
    (a discarded job must not leak momentum/schedule progress)."""
    loss_fn, eval_fn, p0, data = golden_problem
    cfg = _dropout_cfg(async_mode=True, momentum=0.5)
    runs = [runtime.run(loss_fn, p0, data, cfg, eval_fn, eval_every=4)
            for _ in range(2)]
    assert runs[0].trace == runs[1].trace
    assert any(e[1] == "drop" for e in runs[0].trace)
    for a, b in zip(jax.tree.leaves(runs[0].params),
                    jax.tree.leaves(runs[1].params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# AsyncPeriod semantics
# ---------------------------------------------------------------------------

def test_async_suffix_and_make_async_registry():
    algo = get_algorithm("stl_sc+async")
    assert isinstance(algo.sync_policy, AsyncPeriod)
    assert isinstance(algo.sync_policy.base, StagewiseGeometric)
    assert algo.sync_policy.asynchronous
    assert make_async(algo) is algo  # idempotent
    # the schedule is the base policy's, untouched
    cfg = _golden_cfg()
    assert algo.stages(cfg) == get_algorithm("stl_sc").stages(cfg)
    # prox flag and recenter survive the wrap
    nc = get_algorithm("stl_nc1+async")
    assert nc.prox and nc.sync_policy.recenter


def test_async_rejected_by_vmap_simulator(golden_problem):
    loss_fn, eval_fn, p0, data = golden_problem
    with pytest.raises(ValueError, match="EventBackend"):
        simulate.run(loss_fn, p0, data, _golden_cfg(algo="stl_sc+async"),
                     eval_fn)


def test_async_and_adaptive_rejected_by_driver():
    """The pjit driver's (train_step, sync_step) contract is a barriered
    fixed-schedule round — it must refuse rather than silently run the
    wrong semantics under the right algorithm name, and the refusal must
    name the offending policy and point at the backend that CAN run it."""
    from repro.core.stl_sgd import StagewiseDriver

    with pytest.raises(ValueError) as ei:
        StagewiseDriver(TrainConfig(algo="local+async"),
                        lambda s, b, e: (s, {}), lambda s: s)
    msg = str(ei.value)
    assert "AsyncPeriod" in msg           # names the policy
    assert "local+async" in msg           # names the algorithm
    assert "EventBackend" in msg          # points at the right backend
    assert "runtime" in msg

    with pytest.raises(ValueError) as ei:
        StagewiseDriver(TrainConfig(algo="adaptive"),
                        lambda s, b, e: (s, {}), lambda s: s)
    msg = str(ei.value)
    assert "AdaptivePeriod" in msg
    assert "adaptive" in msg
    assert "simulate.run" in msg or "EventBackend" in msg


def test_async_run_rejects_explicit_topology(golden_problem):
    loss_fn, eval_fn, p0, data = golden_problem
    from repro.engine import Hierarchical

    with pytest.raises(ValueError, match="topology"):
        runtime.run(loss_fn, p0, data, _golden_cfg(async_mode=True),
                    eval_fn, topology=Hierarchical(n_pods=2))
    with pytest.raises(ValueError, match="star"):
        runtime.run(loss_fn, p0, data,
                    _golden_cfg(async_mode=True, topology="hier"), eval_fn)


def test_async_homogeneous_tracks_sync_objective(golden_problem):
    """Same work budget, merge-on-arrival: the homogeneous async run lands
    within 1% of the synchronous objective (staleness ≈ 0 ⇒ full-weight
    merges) and consumes the same modeled wall-clock."""
    loss_fn, eval_fn, p0, data = golden_problem
    sync = runtime.run(loss_fn, p0, data, _golden_cfg(), eval_fn,
                       eval_every=8)
    asyn = runtime.run(loss_fn, p0, data, _golden_cfg(async_mode=True),
                       eval_fn, eval_every=8)
    assert asyn.iters == 4 * sync.iters  # per-client steps vs vmapped slots
    drift = abs(asyn.history[-1].value - sync.history[-1].value) \
        / sync.history[-1].value
    assert drift < 0.01, drift
    assert asyn.wall_clock_s == pytest.approx(sync.wall_clock_s)


def test_async_beats_sync_wall_clock_under_stragglers(golden_problem):
    """The table5 acceptance bar in miniature: ≥2× straggler slowdown ⇒
    async wins modeled wall-clock at <1% objective drift."""
    loss_fn, eval_fn, p0, data = golden_problem
    kw = dict(algo="local", T1=64, k1=8.0, n_stages=3,
              straggler_frac=0.25, straggler_slowdown=2.0)
    sync = runtime.run(loss_fn, p0, data, _golden_cfg(**kw), eval_fn,
                       eval_every=8)
    asyn = runtime.run(loss_fn, p0, data,
                       _golden_cfg(async_mode=True, **kw), eval_fn,
                       eval_every=8)
    assert asyn.wall_clock_s < sync.wall_clock_s
    drift = abs(asyn.history[-1].value - sync.history[-1].value) \
        / sync.history[-1].value
    assert drift < 0.01, drift
    # work-conserving: fast clients take more jobs than the straggler
    per_client = {}
    for t, kind, cid in asyn.trace:
        if kind == "compute_done":
            per_client[cid] = per_client.get(cid, 0) + 1
    strag = {c.cid for c in sample_clients(
        4, Heterogeneity(straggler_frac=0.25, straggler_slowdown=2.0,
                         seed=0)) if c.straggler}
    assert strag
    assert max(per_client[c] for c in strag) \
        < max(v for c, v in per_client.items() if c not in strag)


def test_async_int8_messages_track_dense(golden_problem):
    """StalenessWeightedMean reuses the int8 quantize path with per-client
    EF residuals: compressed async lands near dense async, and the engine
    ledger prices the ~4× smaller uploads."""
    loss_fn, eval_fn, p0, data = golden_problem
    dense = runtime.run(loss_fn, p0, data, _golden_cfg(async_mode=True),
                        eval_fn, eval_every=8)
    comp = runtime.run(loss_fn, p0, data,
                       _golden_cfg(async_mode=True, reducer="int8"),
                       eval_fn, eval_every=8)
    assert abs(comp.history[-1].value - dense.history[-1].value) \
        / dense.history[-1].value < 0.01
    assert dense.comm_bytes > 3 * comp.comm_bytes
    assert comp.rounds == dense.rounds


def test_staleness_weighted_mean_unit():
    red = StalenessWeightedMean(decay=0.5)
    assert red.weight(0) == 1.0
    assert red.weight(3) == pytest.approx(0.5)
    assert red.weight(-1) == 1.0  # clamped
    tmpl = {"w": jnp.arange(4.0), "b": jnp.zeros((2,))}
    res = red.client_residual(tmpl)
    assert all(float(jnp.sum(jnp.abs(l))) == 0.0
               for l in jax.tree.leaves(res))
    delta = {"w": jnp.ones((4,)), "b": jnp.full((2,), 2.0)}
    payload, res2 = red.encode(delta, res, jax.random.key(0))
    for a, b in zip(jax.tree.leaves(payload), jax.tree.leaves(delta)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b))
    merged = red.merge(tmpl, payload, staleness=3.0, n_clients=2)
    np.testing.assert_allclose(np.asarray(merged["w"]),
                               np.asarray(tmpl["w"] + 0.25))
    # int8 messages: EF residual carries the lattice error
    red8 = StalenessWeightedMean(decay=0.5, compress="int", bits=8)
    assert red8.name == "staleness-int8"
    p8, r8 = red8.encode(delta, red8.client_residual(tmpl),
                         jax.random.key(1))
    for d, p, r in zip(jax.tree.leaves(delta), jax.tree.leaves(p8),
                       jax.tree.leaves(r8)):
        np.testing.assert_allclose(np.asarray(p + r), np.asarray(d),
                                   rtol=1e-5, atol=1e-6)
    assert red8.message_bytes(tmpl) < red.message_bytes(tmpl)
    assert get_reducer("staleness-int4").bits == 4
    with pytest.raises(ValueError):
        runtime.staleness_reducer_for(TrainConfig(reducer="topk",
                                                  async_mode=True))


# ---------------------------------------------------------------------------
# AdaptivePeriod (divergence-triggered rounds)
# ---------------------------------------------------------------------------

def test_adaptive_registry_and_limits(golden_problem):
    loss_fn, eval_fn, p0, data = golden_problem
    algo = get_algorithm("adaptive")
    assert isinstance(algo.sync_policy, AdaptivePeriod)
    assert algo.sync_policy.adaptive
    cfg = _golden_cfg(algo="adaptive", T1=8, n_stages=2, k1=4.0)

    def rounds_at(threshold):
        a = Algorithm("adaptive_t", AdaptivePeriod(
            base=FixedPeriod(), threshold=threshold))
        eng = Engine(a, cfg)
        be = simulate.VmapSimulatorBackend(loss_fn, p0, data, eval_fn,
                                           eval_every=1)
        hist = eng.run(be)
        return hist[-1].round, hist[-1].iteration

    r_zero, iters = rounds_at(0.0)
    assert r_zero == iters == 16          # threshold 0 ⇒ EveryStep
    r_inf, _ = rounds_at(float("inf"))
    assert r_inf == 4                     # cap-triggered ⇒ ceil(T/k) rounds
    r_mid, _ = rounds_at(3e-4)
    assert r_inf <= r_mid <= r_zero


def test_adaptive_converges_between_sync_and_local(golden_problem):
    loss_fn, eval_fn, p0, data = golden_problem
    cfg = _golden_cfg(algo="adaptive")
    hist = simulate.run(loss_fn, p0, data, cfg, eval_fn, eval_every=8)
    ref = simulate.run(loss_fn, p0, data, _golden_cfg(), eval_fn,
                       eval_every=8)
    # fewer rounds than EveryStep, same iteration budget, ~same objective
    assert hist[-1].iteration == ref[-1].iteration
    assert hist[-1].round < hist[-1].iteration
    assert abs(hist[-1].value - ref[-1].value) / ref[-1].value < 0.01
