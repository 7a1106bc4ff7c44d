"""STL-SGD stagewise driver — the pjit execution backend.

Orchestrates any registered algorithm over (train_step_local, sync_step)
pairs built by ``core.local_sgd``: per stage s the SyncPolicy fixes η_s,
the driver runs T_s local iterations and triggers the parameter-averaging
round every ⌊k_s⌋ steps; for the ^nc variants the loss is the prox
surrogate f^γ centered at the stage-start average.

Since the engine refactor, ``StagewiseDriver.run`` is a thin wrapper: it
hands a ``DriverBackend`` to the same ``repro.engine.Engine`` that drives
the vmapped simulator, so both front-ends consume one stage stream and one
topology-priced comm ledger. The driver is step-function-agnostic — the
tests drive it with tiny CPU models, the launcher with pjit'd multi-pod
steps.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Callable, List, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import TrainConfig
from repro.core.local_sgd import sync_step_topology
from repro.engine.algorithm import get_algorithm
from repro.engine.engine import Engine, StageStatus, config_link, topology_for
from repro.engine.topology import Hierarchical, get_topology
from repro.obs.trace import CAT_COMM, CAT_COMPUTE
from repro.utils.tree import tree_broadcast_leading, tree_mean_leading
from repro.utils.logging import get_logger

log = get_logger("stl_sgd")


def driver_state(params, n_clients: int) -> dict:
    """Stacked {"params", "opt", "step"} driver state from one replica.

    The state layout ``StagewiseDriver`` and ``local_sgd.build_sync_step``
    expect: every client starts from the same ``params``, momentum buffers
    zeroed, step counter 0.
    """
    stacked = tree_broadcast_leading(params, n_clients)
    return {"params": stacked,
            "opt": {"mu": jax.tree.map(jnp.zeros_like, stacked)},
            "step": jnp.zeros((), jnp.int32)}


def make_client_sgd_step(loss_fn, client_data, batch: int, seed: int = 1):
    """Ready-made ``train_step`` over stacked client data shards.

    One vmapped minibatch SGD step per client on its own shard of
    ``client_data`` (a pytree with leading client axis); the minibatch rng
    derives from ``state["step"]`` so the step is pure and the batch
    stream needs no real payload (drive the driver with
    ``itertools.repeat(None)``). The harness behind the hierarchical
    driver demos (``examples/hierarchical_pods.py``,
    ``benchmarks/table4_comm_cost.py``).
    """
    n_clients = jax.tree.leaves(client_data)[0].shape[0]

    def train_step(state, _, eta):
        def client(p, d, r):
            n = jax.tree.leaves(d)[0].shape[0]
            idx = jax.random.randint(r, (batch,), 0, n)
            b = jax.tree.map(lambda a: a[idx], d)
            loss, g = jax.value_and_grad(lambda q: loss_fn(q, b))(p)
            return jax.tree.map(lambda a, gg: a - eta * gg, p, g), loss

        rngs = jax.random.split(
            jax.random.fold_in(jax.random.key(seed), state["step"]),
            n_clients)
        params, losses = jax.vmap(client)(state["params"], client_data, rngs)
        return dict(state, params=params, step=state["step"] + 1), {
            "loss": jnp.mean(losses)}

    return train_step


@dataclass
class StageResult:
    stage: int
    eta: float
    k: int
    iters: int
    rounds: int
    mean_loss: float
    losses: List[float] = field(default_factory=list)   # per local step


@dataclass
class DriverState:
    state: dict                 # {"params","opt","step"} with client axis
    center: Optional[dict] = None  # prox center (^nc)
    results: List[StageResult] = field(default_factory=list)
    rounds_total: int = 0
    iters_total: int = 0
    comm_bytes_total: int = 0      # modeled bytes moved by sync rounds
    comm_time_s: float = 0.0       # α–β modeled wall-clock of those rounds
    # per-(leaf, hop) totals ({"leaf","path","hop","bytes","time_s"}); the
    # streaming round's ledger — sums reconcile with the tree-level totals
    # above (bytes bit-exactly, seconds to float-sum precision)
    leaf_ledger: List[dict] = field(default_factory=list)


class DriverBackend:
    """Engine backend: a stream of pjit step calls on real batches."""

    def __init__(self, driver: "StagewiseDriver", ds: DriverState, batches,
                 max_iters: Optional[int]):
        self.driver = driver
        self.ds = ds
        self.it = iter(batches)
        self.max_iters = max_iters

    def setup(self, engine: Engine):
        template = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape[1:], x.dtype),
            self.ds.state["params"])
        n_clients = jax.tree.leaves(self.ds.state["params"])[0].shape[0]
        engine.set_cost_basis(template, n_clients)

    def run_stage(self, stage, engine: Engine) -> StageStatus:
        """One stage of local steps and rounds. Each place the loop takes
        a batch, launches a program, blocks on the device or reads a value
        back from it has a wall span of its own (``input``, ``dispatch``,
        ``wait``, ``loss_read``), so a profile puts every device idle gap
        down to one of them, and counts the host syncs."""
        drv, ds = self.driver, self.ds
        if drv.uses_center:
            ds.center = tree_mean_leading(ds.state["params"])
        losses = []
        status = StageStatus()
        done = 0
        span = engine.tracer.span
        while done < stage.T:
            burst = min(stage.k, stage.T - done)
            with span("local_steps", cat=CAT_COMPUTE, track="driver",
                      attrs={"s": stage.s, "steps": burst, "eta": stage.eta}):
                for _ in range(burst):
                    with span("step", cat=CAT_COMPUTE, track="driver",
                              step_num=ds.iters_total):
                        with span("input", track="driver"):
                            batch = next(self.it)
                        with span("dispatch", cat=CAT_COMPUTE,
                                  track="driver"):
                            if drv.uses_center:
                                ds.state, m = drv.train_step(
                                    ds.state, batch, stage.eta, ds.center)
                            else:
                                ds.state, m = drv.train_step(
                                    ds.state, batch, stage.eta)
                        with span("wait", track="driver"):
                            jax.block_until_ready(ds.state)
                        with span("loss_read", track="driver"):
                            losses.append(float(m["loss"]))
                    done += 1
                    ds.iters_total += 1
                    if self.max_iters and ds.iters_total >= self.max_iters:
                        break
            with span("reduce", cat=CAT_COMM, track="driver",
                      attrs=dict(drv.span_attrs, s=stage.s)):
                with span("dispatch", cat=CAT_COMM, track="driver"):
                    ds.state = drv.sync_step(ds.state)
                with span("wait", track="driver"):
                    jax.block_until_ready(ds.state)
            status.rounds += 1
            ds.rounds_total += 1
            if self.max_iters and ds.iters_total >= self.max_iters:
                status.stop = True
                break
        status.iters = done
        with span("stage_end", track="driver"):
            mean_loss = float("nan")
            if losses:
                with span("loss_read", track="driver"):
                    mean_loss = float(jnp.mean(jnp.asarray(losses)))
            res = StageResult(stage.s, stage.eta, stage.k, done,
                              status.rounds, mean_loss, losses)
            ds.results.append(res)
            engine.metrics.gauge(
                "train.stage_objective", unit="loss",
                help="mean training loss per stage").set(res.mean_loss,
                                                         stage=res.stage)
            log.info("stage_done", stage=res.stage, eta=res.eta, k=res.k,
                     iters=res.iters, rounds=res.rounds, loss=res.mean_loss)
        return status

    def finish(self, engine: Engine) -> DriverState:
        self.ds.comm_bytes_total = engine.report.comm_bytes_total
        self.ds.comm_time_s = engine.report.comm_time_s
        self.ds.leaf_ledger = engine.leaf_ledger()
        return self.ds


def _pods(topology):
    """``(n_pods, inter-pod reducer name)`` of a two-level round, else
    ``None``."""
    if isinstance(topology, Hierarchical):
        return topology.n_pods, topology.inter.name
    return None


class StagewiseDriver:
    """Runs cfg.algo over a stream of batches.

    train_step(state, batch, eta[, center]) -> (state, metrics)
    sync_step(state) -> state

    The priced round is the ``engine.Topology`` the sync_step carries
    (``local_sgd.build_sync_step`` puts it there) on the config's α–β
    link, or the config's own (``engine.topology_for``) for a step that
    carries none. A config that names a pod topology pins its pods: the
    step must reduce over ``n_pods`` pods with the same inter-pod reducer
    (one pod: the flat star). ``DriverState.comm_bytes_total`` and the
    per-(leaf, hop) ``leaf_ledger`` so describe the collectives the run
    emitted.
    """

    def __init__(self, tcfg: TrainConfig, train_step: Callable,
                 sync_step: Callable, uses_center: bool = False):
        self.tcfg = tcfg
        self.train_step = train_step
        self.sync_step = sync_step
        self.uses_center = uses_center
        configured = topology_for(tcfg)
        carried = sync_step_topology(sync_step)
        # a config that names pods (its spec is two-level at two pods)
        # pins them, one pod (the flat star) included: otherwise the
        # ledger would price an inter-pod hop the round never crosses, or
        # other pods than the round reduces over
        if isinstance(get_topology(tcfg.topology, n_pods=2), Hierarchical) \
                and _pods(carried) != _pods(configured):
            runs = ("no two-level round" if _pods(carried) is None else
                    "%d pods with inter_reducer=%r" % _pods(carried))
            raise ValueError(
                f"topology={tcfg.topology!r} needs the two-level round "
                f"local_sgd.build_sync_step(reducer, hierarchical=True, "
                f"n_pods={tcfg.n_pods}, inter_reducer="
                f"{tcfg.inter_reducer!r}) builds, but the sync step runs "
                f"{runs}: the ledger would price a different round than "
                f"the one executed")
        self.topology = (configured if carried is None
                         else carried.on_link(config_link(tcfg)))
        self.algorithm = get_algorithm(tcfg.algo)
        policy = self.algorithm.sync_policy
        if getattr(policy, "asynchronous", False):
            # the driver's (train_step, sync_step) contract is a barriered
            # fixed-schedule round; running these policies here would
            # silently execute the wrong semantics under the right name
            raise ValueError(
                f"StagewiseDriver runs barriered fixed-schedule rounds, but "
                f"algorithm {self.algorithm.name!r} carries the asynchronous "
                f"{type(policy).__name__} policy (merge-on-arrival, no "
                f"barrier). Run it on the event runtime instead: "
                f"repro.runtime.run / repro.runtime.EventBackend")
        if getattr(policy, "adaptive", False):
            raise ValueError(
                f"StagewiseDriver runs barriered fixed-schedule rounds, but "
                f"algorithm {self.algorithm.name!r} carries the "
                f"{type(policy).__name__} policy, whose divergence probe "
                f"decides each round at runtime. Run it on the vmapped "
                f"simulator (core.simulate.run) or the event runtime "
                f"(repro.runtime.EventBackend)")
        self.stages = self.algorithm.stages(tcfg)
        # trace-span attributes of one sync round — derived from the
        # topology the ledger prices, so trace and ledger agree by
        # construction
        t, pods = self.topology, _pods(self.topology)
        self.span_attrs = {"reducer": (t.intra if pods else t.reducer).name,
                           "streaming": t.streaming,
                           "hierarchical": pods is not None}
        if pods:
            self.span_attrs.update(n_pods=pods[0], inter_reducer=pods[1])

    def run(self, state: dict, batches, max_iters: Optional[int] = None,
            tracer=None, series=None) -> DriverState:
        ds = DriverState(state=state)
        # a fresh Engine per run: its report is the run's comm ledger,
        # priced on exactly the topology the sync_step executes —
        # modeled and executed bytes cannot diverge.
        engine = Engine(self.algorithm, self.tcfg, topology=self.topology,
                        tracer=tracer, series=series)
        ds = engine.run(DriverBackend(self, ds, batches, max_iters))
        log.info("comm_summary", reducer=self.span_attrs["reducer"],
                 rounds=ds.rounds_total, comm_bytes=ds.comm_bytes_total,
                 comm_time_s=ds.comm_time_s)
        return ds
