"""Two-level (hierarchical) sync rounds in the pjit driver.

The decisive invariants (ISSUE 5 acceptance):
  * flat safety rail — with ``n_pods=1`` or a dense inter reducer the
    two-level round is bit-exact with the existing flat round (params,
    opt, and state key set);
  * shared code path — the driver's two-level round IS
    ``engine.Hierarchical.reduce`` (the reduce the vmapped simulator
    executes), so a multi-round driver trace with int8-EF WAN is
    bit-exact with the topology-level replay on the same seed, error
    feedback residuals included;
  * ledger honesty — ``StagewiseDriver`` prices a hierarchical run
    through ``engine.Hierarchical``: the per-(leaf, hop) ledger carries
    two hops per leaf and reconciles bit-exactly (bytes; modeled seconds
    to float-sum precision) with both the run totals and the tree-level
    ``round_bytes``/``round_time``;
  * one round — the config and the topology the sync step carries must
    agree: a flat step under a hierarchical config, mismatched n_pods or
    a mismatched inter-pod reducer are refused with actionable errors;
  * mesh structure — on a (pod, data, model) mesh the two-level round's
    collectives split into data-axis-only (intra-pod) and pod-axis-only
    (inter-pod) traffic, where the flat round moves everything across
    the combined pod+data group (subprocess, 8 host devices).
"""
import json
import math
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.comm import DenseMean, QuantizedMean, get_reducer
from repro.configs.base import TrainConfig
from repro.core import local_sgd as LS
from repro.core.stl_sgd import StagewiseDriver
from repro.engine import Hierarchical, Star, topology_for
from repro.utils.tree import tree_broadcast_leading, tree_mean_leading

N_CLIENTS, N_PODS = 4, 2  # the 2-pod × 2-client grid


def _tree_equal(a, b):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def _state(n=N_CLIENTS, d=12, seed=0, perturb=True):
    key = jax.random.key(seed)
    params = {"w1": jax.random.normal(key, (d, d)),
              "w2": jax.random.normal(jax.random.fold_in(key, 1), (d,))}
    state = {"params": tree_broadcast_leading(params, n),
             "opt": {"mu": jax.tree.map(
                 jnp.zeros_like, tree_broadcast_leading(params, n))},
             "step": jnp.zeros((), jnp.int32)}
    if perturb:  # give every client its own replica so the round works
        state["params"] = jax.tree.map(
            lambda x: x + 0.01 * jax.random.normal(
                jax.random.fold_in(key, x.shape[-1]), x.shape),
            state["params"])
    return state


def _drift(state, eta=0.1):
    """Deterministic per-client local step (signature-compatible toy)."""
    params = jax.tree.map(
        lambda x: x * (1.0 - 0.01 * eta)
        + 0.001 * jnp.arange(x.shape[0], dtype=x.dtype).reshape(
            (x.shape[0],) + (1,) * (x.ndim - 1)),
        state["params"])
    return dict(state, params=params, step=state["step"] + 1)


def _toy_train_step(state, batch, eta):
    return _drift(state, eta), {"loss": jnp.zeros(())}


# ---------------------------------------------------------------------------
# Flat safety rail: n_pods=1 and dense∘dense collapse bit-exactly
# ---------------------------------------------------------------------------

def test_two_level_dense_wan_bit_exact_with_flat_round():
    state = _state()
    flat = jax.jit(LS.build_sync_step(None))
    hier = jax.jit(LS.build_sync_step(None, hierarchical=True,
                                      n_pods=N_PODS, inter_reducer="dense"))
    out_f, out_h = flat(state), hier(state)
    assert set(out_f.keys()) == set(out_h.keys())  # no stray comm state
    _tree_equal(out_f, out_h)


def test_two_level_single_pod_bit_exact_with_flat_round():
    """One pod has no inter-pod link: the round degenerates to the flat
    round with the intra reducer, inter reducer unused."""
    state = _state()
    flat = jax.jit(LS.build_sync_step(None))
    hier = jax.jit(LS.build_sync_step(None, hierarchical=True, n_pods=1,
                                      inter_reducer="int8"))
    _tree_equal(flat(state), hier(state))
    assert type(LS.build_sync_step(None, hierarchical=True,
                                   n_pods=1).topology) is Star


def test_hierarchical_dense_dense_collapses_to_flat_mean():
    """Topology level: dense∘dense is computed AS the flat mean (bit-exact,
    not merely allclose) — the contract the driver's rail relies on."""
    stacked = _state(n=8)["params"]
    topo = Hierarchical(n_pods=2, intra=DenseMean(), inter=DenseMean())
    assert not topo.stateful
    mean, _ = topo.reduce(stacked, topo.init_state(stacked),
                          jax.random.key(1))
    _tree_equal(mean, tree_mean_leading(stacked))
    assert Hierarchical(n_pods=2, inter=QuantizedMean()).stateful


def test_two_level_rejects_indivisible_clients():
    sync = LS.build_sync_step(None, hierarchical=True, n_pods=N_PODS)
    with pytest.raises(ValueError, match="divisible"):
        sync(_state(n=5))


# ---------------------------------------------------------------------------
# Shared code path: driver round ≡ engine.Hierarchical.reduce (same seed)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("inter", ["dense", "int8"])
def test_driver_trace_bit_exact_with_hierarchical_replay(inter):
    """2-pod × 2-client trace: StagewiseDriver with the two-level sync step
    vs a replay of the same schedule through ``Hierarchical.reduce`` (the
    simulator's hierarchical round) with the driver's rng rule — params and
    EF state bit-identical after every stage."""
    tcfg = TrainConfig(algo="local", T1=8, k1=2.0, n_stages=2,
                       topology="hier", n_pods=N_PODS, inter_reducer=inter)
    sync_step = LS.build_sync_step(None, hierarchical=True, n_pods=N_PODS,
                                   inter_reducer=inter)
    drv = StagewiseDriver(tcfg, _toy_train_step, sync_step)
    assert isinstance(drv.topology, Hierarchical)
    assert drv.topology.n_pods == N_PODS
    ds = drv.run(_state(), iter([None] * 256))

    # replay: same stage stream, same drift, sync via the topology the
    # simulator executes, rng = fold_in(key(base_seed=0), step)
    topo = Hierarchical(n_pods=N_PODS, intra=get_reducer(None),
                        inter=get_reducer(inter))
    state, comm = _state(), None
    rounds = 0
    for stage in drv.stages:
        done = 0
        while done < stage.T:
            for _ in range(min(stage.k, stage.T - done)):
                state = _drift(state, stage.eta)
                done += 1
            rng = jax.random.fold_in(jax.random.key(0), state["step"])
            if not topo.stateful:
                consensus, _ = topo.reduce(state["params"], None, rng)
            else:
                if comm is None:
                    comm = topo.init_state(state["params"])
                consensus, comm = topo.reduce(state["params"], comm, rng)
            state = dict(state, params=tree_broadcast_leading(
                consensus, N_CLIENTS))
            rounds += 1
    assert ds.rounds_total == rounds
    _tree_equal(ds.state["params"], state["params"])
    if inter != "dense":
        _tree_equal(ds.state["comm"], comm)
    else:
        assert "comm" not in ds.state  # flat contract: state untouched


# ---------------------------------------------------------------------------
# Ledger: two hops per leaf, reconciled against tree totals
# ---------------------------------------------------------------------------

def test_driver_hierarchical_leaf_ledger_reconciles():
    tcfg = TrainConfig(algo="local", T1=8, k1=2.0, n_stages=1,
                       topology="hier", n_pods=N_PODS, inter_reducer="int8")
    sync_step = LS.build_sync_step(None, hierarchical=True, n_pods=N_PODS,
                                   inter_reducer="int8")
    drv = StagewiseDriver(tcfg, _toy_train_step, sync_step)
    ds = drv.run(_state(), iter([None] * 64))
    assert ds.rounds_total == 4
    template = jax.tree.map(lambda x: x[0], _state()["params"])
    n_leaves = len(jax.tree.leaves(template))
    assert len(ds.leaf_ledger) == 2 * n_leaves
    assert {l["hop"] for l in ds.leaf_ledger} == {"intra_pod", "inter_pod"}
    # per-leaf totals reconcile with the run totals (bytes bit-exactly,
    # modeled seconds to float-sum precision) ...
    assert sum(l["bytes"] for l in ds.leaf_ledger) == ds.comm_bytes_total
    assert math.fsum(l["time_s"] for l in ds.leaf_ledger) \
        == pytest.approx(ds.comm_time_s, rel=1e-12)
    # ... and the run totals with the Hierarchical tree-level price of the
    # config's topology (the modeled-vs-executed byte agreement)
    topo = topology_for(tcfg)
    assert isinstance(topo, Hierarchical)
    assert ds.comm_bytes_total \
        == topo.round_bytes(template, N_CLIENTS) * ds.rounds_total
    intra = sum(l["bytes"] for l in ds.leaf_ledger
                if l["hop"] == "intra_pod")
    hop_bytes = {h.hop: h.bytes for h in topo.hop_costs(template, N_CLIENTS)}
    assert intra == hop_bytes["intra_pod"] * ds.rounds_total


# ---------------------------------------------------------------------------
# One round: the config and the sync step's topology must agree
# ---------------------------------------------------------------------------

def test_driver_refuses_flat_step_under_hierarchical_config():
    tcfg = TrainConfig(algo="local", topology="hier", n_pods=N_PODS)
    with pytest.raises(ValueError, match="build_sync_step"):
        StagewiseDriver(tcfg, _toy_train_step, LS.build_sync_step(None))


def test_driver_refuses_n_pods_mismatch():
    tcfg = TrainConfig(algo="local", topology="hier", n_pods=4)
    sync = LS.build_sync_step(None, hierarchical=True, n_pods=N_PODS)
    with pytest.raises(ValueError, match="n_pods"):
        StagewiseDriver(tcfg, _toy_train_step, sync)


def test_driver_refuses_inter_reducer_mismatch():
    """cfg-derived reports (comm_summary_for) and the executed ledger must
    price the same WAN hop — a dense-vs-int8 mismatch would silently
    diverge modeled from executed bytes."""
    tcfg = TrainConfig(algo="local", topology="hier", n_pods=N_PODS,
                       inter_reducer="dense")
    sync = LS.build_sync_step(None, hierarchical=True, n_pods=N_PODS,
                              inter_reducer="int8")
    with pytest.raises(ValueError, match="inter_reducer"):
        StagewiseDriver(tcfg, _toy_train_step, sync)


def test_hier_tagged_step_implies_hierarchical_under_star_config():
    """Under a star config the executed round wins, and the ledger
    follows it (read through jit's wrapper)."""
    sync = jax.jit(LS.build_sync_step(None, hierarchical=True,
                                      n_pods=N_PODS, inter_reducer="int8"))
    drv = StagewiseDriver(TrainConfig(algo="local", T1=4, k1=2.0,
                                      n_stages=1), _toy_train_step, sync)
    assert isinstance(drv.topology, Hierarchical)
    assert drv.topology.n_pods == N_PODS
    assert drv.topology.inter.name == "int8"
    ds = drv.run(_state(), iter([None] * 32))
    assert {l["hop"] for l in ds.leaf_ledger} == {"intra_pod", "inter_pod"}


def test_single_pod_config_runs_flat():
    """n_pods=1 under topology='hier' is the flat degenerate case — both
    the sync step and the pricing fall back to the star round."""
    tcfg = TrainConfig(algo="local", T1=4, k1=2.0, n_stages=1,
                       topology="hier", n_pods=1)
    sync = LS.build_sync_step(None, hierarchical=True, n_pods=1)
    drv = StagewiseDriver(tcfg, _toy_train_step, sync)
    assert type(drv.topology) is Star
    ds = drv.run(_state(), iter([None] * 32))
    assert {l["hop"] for l in ds.leaf_ledger} == {"uplink"}


def test_streaming_hierarchical_driver_composes():
    """streaming=True now composes with hierarchical=True: the driver runs
    the per-leaf two-level round, bit-exact with the blocking one, under
    the streaming-hier topology spec, pricing both hops."""
    sync_b = LS.build_sync_step("int8", hierarchical=True,
                                n_pods=N_PODS, inter_reducer="int8")
    sync_s = LS.build_sync_step("int8", streaming=True,
                                hierarchical=True, n_pods=N_PODS,
                                inter_reducer="int8")
    assert sync_s.topology.streaming
    assert isinstance(sync_s.topology, Hierarchical)
    cfg = dict(algo="local", T1=4, k1=2.0, n_stages=1, reducer="int8")
    drv_b = StagewiseDriver(TrainConfig(**cfg, topology="hier",
                                        n_pods=N_PODS), _toy_train_step,
                            sync_b)
    drv_s = StagewiseDriver(TrainConfig(**cfg, topology="streaming-hier",
                                        n_pods=N_PODS), _toy_train_step,
                            sync_s)
    assert drv_s.topology.name == "streaming-hier"
    ds_b = drv_b.run(_state(), iter([None] * 32))
    ds_s = drv_s.run(_state(), iter([None] * 32))
    assert jax.tree.all(jax.tree.map(
        lambda a, b: bool((a == b).all()),
        ds_b.state["params"], ds_s.state["params"]))
    assert jax.tree.all(jax.tree.map(
        lambda a, b: bool((a == b).all()),
        ds_b.state["comm"], ds_s.state["comm"]))
    # the streaming ledger prices the identical two-level round
    assert ds_s.comm_bytes_total == ds_b.comm_bytes_total
    assert {l["hop"] for l in ds_s.leaf_ledger} == {"intra_pod", "inter_pod"}
    assert sum(l["bytes"] for l in ds_s.leaf_ledger) == ds_s.comm_bytes_total


def test_sync_step_runs_a_given_topology():
    """A Topology handed to build_sync_step (as the launcher hands the
    config's) is the round it runs and carries: its pods need no pod mesh
    axis, and the round is the keyword-built one bit for bit."""
    topo = topology_for(TrainConfig(topology="hier", n_pods=N_PODS,
                                    inter_reducer="int8"))
    step = LS.build_sync_step(topo)
    assert step.topology is topo
    ref = LS.build_sync_step(None, hierarchical=True, n_pods=N_PODS,
                             inter_reducer="int8")
    _tree_equal(jax.jit(step)(_state()), jax.jit(ref)(_state()))


def test_build_train_steps_two_level_needs_pod_axis():
    from repro.configs import get_arch
    from repro.launch.mesh import make_host_mesh

    with pytest.raises(ValueError, match="pod"):
        LS.build_train_steps(get_arch("qwen3-14b", smoke=True),
                             make_host_mesh(1, 1), client_axis="data",
                             inter_reducer="int8")


# ---------------------------------------------------------------------------
# Mesh structure: intra hop on the data axis, inter hop on the pod axis
# ---------------------------------------------------------------------------

SRC = os.path.join(os.path.dirname(__file__), "..", "src")

_MESH_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys; sys.path.insert(0, "@SRC@")
import jax, jax.numpy as jnp, json
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.core import local_sgd as LS
from repro.launch import hlo_analysis as H
from repro.launch.mesh import make_host_pod_mesh

mesh = make_host_pod_mesh(pods=2, data=2, model=2)
C = 4
key = jax.random.key(0)
params = {"w1": jax.random.normal(key, (C, 32, 8)),
          "w2": jax.random.normal(jax.random.fold_in(key, 1), (C, 8))}
state = {"params": params,
         "opt": {"mu": jax.tree.map(jnp.zeros_like, params)},
         "step": jnp.zeros((), jnp.int32)}
rep = NamedSharding(mesh, P(("pod", "data")))
st_sh = {"params": jax.tree.map(lambda _: rep, params),
         "opt": {"mu": jax.tree.map(lambda _: rep, params)},
         "step": NamedSharding(mesh, P())}
shape_d = dict(zip(mesh.axis_names, mesh.devices.shape))
out = {}
with jax.sharding.set_mesh(mesh):
    for name, step in [
            ("flat", LS.build_sync_step(None)),
            ("hier", LS.build_sync_step(None, hierarchical=True, n_pods=2,
                                        inter_reducer="int8"))]:
        compiled = jax.jit(step, in_shardings=(st_sh,)).lower(state).compile()
        colls = H.parse_collectives_nested(compiled.as_text(), shape_d)
        out[name] = H.collective_summary(colls)["by_axes"]
print(json.dumps(out))
"""


_TRAIN_STEPS_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys; sys.path.insert(0, "@SRC@")
import dataclasses, jax, json
from repro.configs import get_arch, SHAPES
from repro.core import local_sgd as LS
from repro.launch import hlo_analysis as H
from repro.launch.mesh import make_host_pod_mesh
from repro.launch.specs import train_specs

mesh = make_host_pod_mesh(pods=2, data=2, model=2)
cfg = get_arch("qwen3-14b", smoke=True)
shape = dataclasses.replace(SHAPES["train_4k"], seq_len=64, global_batch=8)
state, batch, st_sh, b_sh, ca = train_specs(cfg, shape, mesh)
assert tuple(ca) == ("pod", "data"), ca
with jax.sharding.set_mesh(mesh):
    local_step, sync_step, _ = LS.build_train_steps(
        cfg, mesh, client_axis=ca, microbatch=1, inter_reducer="int8")
    assert sync_step.topology.name == "hierarchical"
    assert sync_step.topology.n_pods == 2
    assert sync_step.topology.inter.name == "int8"
    cl = jax.jit(local_step, in_shardings=(st_sh, b_sh, None),
                 out_shardings=(st_sh, None)).lower(state, batch,
                                                    0.1).compile()
    cs = jax.jit(sync_step, in_shardings=(st_sh,)).lower(state).compile()
shape_d = dict(zip(mesh.axis_names, mesh.devices.shape))
out = {n: H.collective_summary(
           H.parse_collectives_nested(c.as_text(), shape_d))["by_axes"]
       for n, c in [("local", cl), ("sync", cs)]}
print(json.dumps(out))
"""


def test_build_train_steps_two_level_positive_path():
    """The advertised entry point — ``build_train_steps(client_axis=
    ("pod", "data"), inter_reducer=...)`` on a real multi-pod mesh —
    lowers and compiles end-to-end: the tuple-spmd local step keeps the
    client grid collective-free, the derived sync step is two-level
    (intra traffic on data, inter traffic on pod)."""
    script = _TRAIN_STEPS_SCRIPT.replace("@SRC@", os.path.abspath(SRC))
    out = subprocess.run([sys.executable, "-c", script],
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    # local step: client-grid traffic is control-plane only (loss scalars)
    client_bytes = sum(v for k, v in res["local"].items()
                       if "pod" in k or "data" in k)
    assert client_bytes < 1e5, res["local"]
    # sync step: real two-level traffic, split by axis
    assert sum(v for k, v in res["sync"].items() if k == "data") > 1e5, \
        res["sync"]
    assert sum(v for k, v in res["sync"].items() if k == "pod") > 0, \
        res["sync"]


def test_two_level_sync_collectives_split_by_mesh_axis():
    """Compile both sync rounds on a (pod=2, data=2, model=2) host mesh:
    the two-level round must move intra-pod traffic on the data axis and
    inter-pod traffic on the pod axis as *separate* collective groups; the
    flat round has no pod-only reduction (everything crosses the combined
    client group)."""
    script = _MESH_SCRIPT.replace("@SRC@", os.path.abspath(SRC))
    out = subprocess.run([sys.executable, "-c", script],
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    hier, flat = res["hier"], res["flat"]
    data_only = sum(v for k, v in hier.items() if k == "data")
    pod_only = sum(v for k, v in hier.items() if k == "pod")
    assert data_only > 0, hier    # intra-pod reduce rides the data axis
    assert pod_only > 0, hier     # inter-pod hop rides the pod axis
    assert sum(v for k, v in flat.items() if k == "pod") == 0, flat
    # the flat round's client average spans pod+data as one group
    assert sum(v for k, v in flat.items() if "pod" in k and "data" in k) > 0, \
        flat
