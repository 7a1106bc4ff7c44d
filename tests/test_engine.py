"""repro.engine: registry round-trips, bit-exact regression, topologies.

The decisive invariants:
  * every historical algo name resolves through the registry to an
    Algorithm whose SyncPolicy reproduces the old make_stages schedule;
  * stl_sc + DenseMean under the Engine reproduces the recorded
    ``simulate.run`` objective traces bit-exactly (``tests/goldens.py``);
  * the previously untested algorithms (stl_nc2, crpsgd) run end-to-end
    through both backends (vmapped simulator and StagewiseDriver);
  * the Hierarchical topology composes a dense intra-pod reduce with a
    compressed inter-pod reduce, reports per-hop α–β costs, and its
    error feedback converges to the dense consensus.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import goldens as G
from repro.comm import DenseMean, NetworkModel, QuantizedMean, comm_summary_for
from repro.configs.base import TrainConfig
from repro.core import schedules as S
from repro.core import simulate
from repro.core.stl_sgd import StagewiseDriver
from repro.engine import (
    Engine,
    EveryStep,
    FixedPeriod,
    GrowingBatchUpdate,
    Hierarchical,
    LargeBatchUpdate,
    SgdUpdate,
    StagewiseGeometric,
    StagewiseLinear,
    Star,
    algorithm_names,
    get_algorithm,
    get_topology,
    topology_for,
)
from repro.utils.tree import tree_broadcast_leading, tree_mean_leading

ALL_ALGOS = ("sync", "lb", "crpsgd", "local", "stl_sc", "stl_nc1", "stl_nc2")


# ---------------------------------------------------------------------------
# Registry round-trips
# ---------------------------------------------------------------------------

def test_registry_knows_all_seven_names():
    assert set(ALL_ALGOS) <= set(algorithm_names())
    for name in ALL_ALGOS:
        algo = get_algorithm(name)
        assert algo.name == name
        assert get_algorithm(algo) is algo  # Algorithm passes through


def test_registry_rejects_unknown():
    with pytest.raises(ValueError):
        get_algorithm("bogus")


def test_registry_policy_and_update_composition():
    assert isinstance(get_algorithm("sync").sync_policy, EveryStep)
    assert isinstance(get_algorithm("lb").local_update, LargeBatchUpdate)
    assert isinstance(get_algorithm("crpsgd").local_update,
                      GrowingBatchUpdate)
    assert isinstance(get_algorithm("local").sync_policy, FixedPeriod)
    assert isinstance(get_algorithm("stl_sc").sync_policy,
                      StagewiseGeometric)
    assert isinstance(get_algorithm("stl_nc1").sync_policy,
                      StagewiseGeometric)
    assert isinstance(get_algorithm("stl_nc2").sync_policy, StagewiseLinear)
    # prox-center policy: only ^nc re-centers, and only with gamma_inv > 0
    assert get_algorithm("stl_nc1").sync_policy.recenter
    assert not get_algorithm("stl_sc").sync_policy.recenter
    cfg = TrainConfig(algo="stl_nc1", gamma_inv=0.1)
    assert get_algorithm("stl_nc1").uses_center(cfg)
    assert not get_algorithm("stl_nc1").uses_center(
        TrainConfig(algo="stl_nc1", gamma_inv=0.0))
    assert not get_algorithm("stl_sc").uses_center(cfg)


@pytest.mark.parametrize("algo", ALL_ALGOS)
@pytest.mark.parametrize("iid", [True, False])
def test_policy_stages_match_make_stages(algo, iid):
    """make_stages (the historical entry point) and the SyncPolicy agree."""
    via_name = S.make_stages(algo, 0.4, 100, 4.0, 5, iid)
    via_policy = get_algorithm(algo).sync_policy.stages(0.4, 100, 4.0, 5, iid)
    assert via_name == via_policy
    assert len(via_name) == 5
    assert all(st.k >= 1 for st in via_name)


def test_local_update_batch_rules():
    cfg = TrainConfig(batch_per_client=32, max_batch=512, batch_growth=1.1)
    assert SgdUpdate().round_batch(cfg) == 32
    assert LargeBatchUpdate().round_batch(cfg) == 128   # ×4, the lb rule
    assert GrowingBatchUpdate().round_batch(cfg) == 512  # masked max buffer
    assert SgdUpdate().growth(cfg) == 1.0
    assert GrowingBatchUpdate().growth(cfg) == pytest.approx(1.1)


# ---------------------------------------------------------------------------
# Bit-exact regression: Engine vs the recorded simulate.run traces
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def golden_problem():
    return G.golden_problem()


def test_engine_stl_sc_dense_bit_exact_with_pre_refactor_trace(golden_problem):
    loss_fn, eval_fn, p0, data = golden_problem
    hist = simulate.run(loss_fn, p0, data, TrainConfig(**G.STL_SC_CFG),
                        eval_fn, **G.STL_SC_RUN)
    assert G.trace(hist) == G.GOLDEN_STL_SC


def test_engine_stl_sc_momentum_chunked_bit_exact(golden_problem):
    loss_fn, eval_fn, p0, data = golden_problem
    hist = simulate.run(loss_fn, p0, data, TrainConfig(**G.STL_SC_MOM_CFG),
                        eval_fn, **G.STL_SC_MOM_RUN)
    assert G.trace(hist) == G.GOLDEN_STL_SC_MOM


# ---------------------------------------------------------------------------
# Previously-untested algorithms end-to-end through the engine
# ---------------------------------------------------------------------------

def test_crpsgd_simulator_end_to_end(golden_problem):
    loss_fn, eval_fn, p0, data = golden_problem
    cfg = TrainConfig(algo="crpsgd", eta1=0.5, T1=64, k1=1.0, n_stages=3,
                      iid=True, batch_per_client=8, batch_growth=1.05,
                      max_batch=32, seed=0)
    hist = simulate.run(loss_fn, p0, data, cfg, eval_fn, eval_every=16)
    assert hist[-1].value < hist[0].value * 0.8
    # EveryStep policy: one round per iteration
    assert hist[-1].round == hist[-1].iteration


def test_stl_nc2_simulator_end_to_end(golden_problem):
    loss_fn, eval_fn, p0, data = golden_problem
    cfg = TrainConfig(algo="stl_nc2", eta1=0.4, T1=32, k1=2.0, n_stages=4,
                      iid=True, gamma_inv=0.2, batch_per_client=8, seed=0)
    hist = simulate.run(loss_fn, p0, data, cfg, eval_fn, eval_every=8)
    assert hist[-1].value < hist[0].value * 0.9
    # linear policy: T_s = s·T1 ⇒ total iters = T1·S(S+1)/2
    assert hist[-1].iteration == 32 * (1 + 2 + 3 + 4)


def _toy_driver(algo, uses_center=False, **cfg_kw):
    """Tiny quadratic client model through the real StagewiseDriver."""
    C, d = 4, 8
    key = jax.random.key(0)
    target = jax.random.normal(key, (d,))

    def train_step(state, batch, eta, center=None):
        def per_client(p, b):
            g = p - target + 0.01 * b
            if center is not None:
                g = g + 0.2 * (p - center)
            return p - eta * g
        params = jax.vmap(per_client)(state["params"], batch)
        loss = float(jnp.mean(jnp.square(params - target)))
        return dict(state, params=params, step=state["step"] + 1), {
            "loss": jnp.asarray(loss)}

    def sync_step(state):
        mean = tree_mean_leading(state["params"])
        return dict(state, params=tree_broadcast_leading(mean, C))

    def batches():
        rng = np.random.RandomState(0)
        while True:
            yield jnp.asarray(rng.randn(C, d).astype(np.float32))

    tcfg = TrainConfig(algo=algo, **cfg_kw)
    state = {"params": jnp.zeros((C, d)), "step": jnp.zeros((), jnp.int32)}
    drv = StagewiseDriver(tcfg, train_step, sync_step,
                          uses_center=uses_center)
    return drv.run(state, batches()), target


def test_crpsgd_driver_end_to_end():
    ds, target = _toy_driver("crpsgd", eta1=0.1, T1=32, k1=1.0, n_stages=2)
    assert ds.iters_total == 64
    assert ds.rounds_total == 64  # k=1: every step syncs
    err = float(jnp.max(jnp.abs(ds.state["params"][0] - target)))
    assert err < 0.2, err
    assert ds.comm_bytes_total > 0 and ds.comm_time_s > 0


def test_stl_nc2_driver_end_to_end():
    ds, target = _toy_driver("stl_nc2", uses_center=True, eta1=0.2, T1=16,
                             k1=2.0, n_stages=3, gamma_inv=0.1)
    # linear schedule: iters = 16·(1+2+3), rounds = Σ ceil(T_s/k_s)
    assert ds.iters_total == 16 * 6
    stages = S.make_stages("stl_nc2", 0.2, 16, 2.0, 3, True)
    assert ds.rounds_total == sum(-(-st.T // st.k) for st in stages)
    assert ds.center is not None  # prox center was re-set per stage
    err = float(jnp.max(jnp.abs(ds.state["params"][0] - target)))
    assert err < 0.2, err


def test_driver_accounting_matches_comm_summary():
    """The engine ledger and the post-hoc comm_summary_for agree."""
    ds, _ = _toy_driver("local", eta1=0.1, T1=8, k1=2.0, n_stages=2)
    cfg = TrainConfig(algo="local", T1=8, k1=2.0, n_stages=2)
    tmpl = {"params": jax.ShapeDtypeStruct((8,), jnp.float32)}
    summ = comm_summary_for(cfg, tmpl["params"], 4, ds.rounds_total)
    assert ds.comm_bytes_total == summ["total_bytes"]
    assert ds.comm_time_s == pytest.approx(summ["total_time_s"])


# ---------------------------------------------------------------------------
# Topology: Star bit-compat, Hierarchical composition + per-hop costs
# ---------------------------------------------------------------------------

def _stacked(n=8):
    k1, k2 = jax.random.split(jax.random.key(0))
    return {"w": jax.random.normal(k1, (n, 17, 3)),
            "b": jax.random.normal(k2, (n, 5))}


def test_star_dense_is_plain_mean():
    stacked = _stacked()
    topo = Star(reducer=DenseMean())
    mean, _ = topo.reduce(stacked, topo.init_state(stacked),
                          jax.random.key(1))
    ref = tree_mean_leading(stacked)
    for a, b in zip(jax.tree.leaves(mean), jax.tree.leaves(ref)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_hierarchical_dense_dense_matches_global_mean():
    stacked = _stacked()
    topo = Hierarchical(n_pods=2, intra=DenseMean(), inter=DenseMean())
    mean, _ = topo.reduce(stacked, topo.init_state(stacked),
                          jax.random.key(1))
    ref = tree_mean_leading(stacked)
    for a, b in zip(jax.tree.leaves(mean), jax.tree.leaves(ref)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-6, atol=1e-6)


def test_hierarchical_int8_inter_ef_converges_to_dense():
    """Dense intra-pod + int8-EF inter-pod: repeated rounds at a fixed
    divergence drain the residual onto the dense consensus."""
    stacked = _stacked()
    topo = Hierarchical(n_pods=2, intra=DenseMean(),
                        inter=QuantizedMean(bits=8))
    state = topo.init_state(stacked)
    target = tree_mean_leading(stacked)
    mean, state = topo.reduce(stacked, state, jax.random.key(2))
    for i in range(12):
        mean, state = topo.reduce(tree_broadcast_leading(mean, 8), state,
                                  jax.random.key(3 + i))
    err = max(float(jnp.max(jnp.abs(a - b)))
              for a, b in zip(jax.tree.leaves(mean),
                              jax.tree.leaves(target)))
    assert err < 1e-3, err


def test_hierarchical_reduce_is_jit_and_scan_safe():
    stacked = _stacked()
    topo = Hierarchical(n_pods=2, intra=DenseMean(),
                        inter=QuantizedMean(bits=8))

    def body(carry, rng):
        mean, carry = topo.reduce(stacked, carry, rng)
        return carry, mean["b"].sum()

    _, out = jax.jit(lambda s: jax.lax.scan(
        body, s, jax.random.split(jax.random.key(0), 3)))(
            topo.init_state(stacked))
    assert out.shape == (3,) and bool(jnp.all(jnp.isfinite(out)))


def test_hierarchical_rejects_indivisible_pods():
    stacked = _stacked(n=6)
    with pytest.raises(ValueError):
        Hierarchical(n_pods=4).init_state(stacked)


def test_hop_costs_per_hop_networks():
    tmpl = {"w": jax.ShapeDtypeStruct((1000,), jnp.float32)}
    topo = Hierarchical(
        n_pods=2, intra=DenseMean(), inter=QuantizedMean(bits=8),
        intra_net=NetworkModel(latency_s=1e-6, bandwidth_gbps=400.0),
        inter_net=NetworkModel(latency_s=5e-3, bandwidth_gbps=1.0))
    hops = topo.hop_costs(tmpl, n_clients=8)
    assert [h.hop for h in hops] == ["intra_pod", "inter_pod"]
    intra, inter = hops
    assert intra.bytes == 8 * 4000            # dense f32 uplink × 8 clients
    assert inter.bytes == 2 * (1000 + 4)      # int8 codes + scale, × 2 pods
    # intra pods reduce in parallel: time prices one pod's 4 messages
    assert intra.time_s == pytest.approx(1e-6 + 4 * 4000 / (400e9 / 8))
    assert inter.time_s == pytest.approx(5e-3 + inter.bytes / (1e9 / 8))
    assert topo.round_bytes(tmpl, 8) == intra.bytes + inter.bytes
    assert topo.round_time(tmpl, 8) == pytest.approx(
        intra.time_s + inter.time_s)
    summ = topo.summary(tmpl, 8, 10)
    assert summ["total_bytes"] == 10 * topo.round_bytes(tmpl, 8)
    assert len(summ["hops"]) == 2
    assert summ["hops"][1]["reducer"] == "int8"


def test_get_topology_specs():
    star = get_topology("star", reducer="dense")
    assert isinstance(star, Star) and isinstance(star.reducer, DenseMean)
    hier = get_topology("hier", reducer="dense", n_pods=4,
                        inter_reducer="int4")
    assert isinstance(hier, Hierarchical)
    assert hier.n_pods == 4 and hier.inter.bits == 4
    assert get_topology(star) is star
    with pytest.raises(ValueError):
        get_topology("ring")
    cfg = TrainConfig(topology="hier", n_pods=2, inter_reducer="int8")
    assert isinstance(topology_for(cfg), Hierarchical)
    assert isinstance(topology_for(TrainConfig()), Star)


def test_simulator_hierarchical_topology_end_to_end(golden_problem):
    """stl_sc over 2 pods (dense ICI + int8 WAN) lands on the flat-dense
    objective — the engine acceptance demo, in miniature."""
    loss_fn, eval_fn, p0, data = golden_problem
    base = dict(algo="stl_sc", eta1=0.5, T1=16, k1=2.0, n_stages=4,
                iid=True, batch_per_client=8, seed=0)
    h_flat = simulate.run(loss_fn, p0, data, TrainConfig(**base), eval_fn,
                          eval_every=8)
    cfg_h = TrainConfig(topology="hier", n_pods=2, inter_reducer="int8",
                        **base)
    h_hier = simulate.run(loss_fn, p0, data, cfg_h, eval_fn, eval_every=8)
    assert abs(h_hier[-1].value - h_flat[-1].value) < 5e-3
    summ = topology_for(cfg_h).summary(p0, 4, h_hier[-1].round)
    assert [h["hop"] for h in summ["hops"]] == ["intra_pod", "inter_pod"]
    assert summ["hops"][0]["bandwidth_gbps"] > summ["hops"][1]["bandwidth_gbps"]


# ---------------------------------------------------------------------------
# Engine plumbing
# ---------------------------------------------------------------------------

def test_engine_requires_cost_basis():
    class BadBackend:
        def setup(self, engine):
            pass

    eng = Engine("sync", TrainConfig(algo="sync", n_stages=1))
    with pytest.raises(RuntimeError):
        eng.run(BadBackend())


def test_engine_report_ledger(golden_problem):
    loss_fn, eval_fn, p0, data = golden_problem
    cfg = TrainConfig(algo="local", eta1=0.3, T1=8, k1=2.0, n_stages=2,
                      iid=True, batch_per_client=8, seed=0)
    eng = Engine(cfg.algo, cfg)
    backend = simulate.VmapSimulatorBackend(loss_fn, p0, data, eval_fn,
                                            eval_every=4)
    hist = eng.run(backend)
    assert eng.report.rounds_total == hist[-1].round == 8
    assert eng.report.iters_total == hist[-1].iteration == 16
    assert eng.report.stages_run == 2
    summ = comm_summary_for(cfg, p0, 4, 8)
    assert eng.report.comm_bytes_total == summ["total_bytes"]
    assert eng.report.comm_time_s == pytest.approx(summ["total_time_s"])
