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

from repro.comm import NetworkModel, get_reducer, link_model
from repro.configs.base import TrainConfig
from repro.core.local_sgd import sync_step_tags
from repro.engine.algorithm import get_algorithm
from repro.engine.engine import Engine, StageStatus
from repro.engine.topology import Hierarchical, Star, StreamingStar
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


class StagewiseDriver:
    """Runs cfg.algo over a stream of batches.

    train_step(state, batch, eta[, center]) -> (state, metrics)
    sync_step(state) -> state

    The sync round's *shape* follows the sync_step's tags (set by
    ``local_sgd.build_sync_step``; explicit args and ``tcfg.topology``
    must agree with them): flat star (default), per-leaf streaming star
    (``streaming=True``), or the two-level hierarchical round
    (``hierarchical=True`` — dense intra-pod over ``data``, compressed
    inter-pod over ``pod``; ``tcfg.n_pods`` / ``tcfg.inter_reducer``).
    The engine then prices exactly that topology, so
    ``DriverState.comm_bytes_total`` and the per-(leaf, hop)
    ``leaf_ledger`` always describe the collectives the run emitted.
    """

    def __init__(self, tcfg: TrainConfig, train_step: Callable,
                 sync_step: Callable, uses_center: bool = False,
                 reducer=None):
        self.tcfg = tcfg
        self.train_step = train_step
        self.sync_step = sync_step
        self.uses_center = uses_center
        # Comm accounting reducer, in priority order: explicit arg > the
        # reducer the sync_step itself was built with (local_sgd.
        # build_sync_step tags it, surviving jax.jit via __wrapped__) >
        # tcfg.reducer. The tag keeps accounting from silently diverging
        # from what the round actually transmits — the driver prices
        # exactly the topology the sync_step executes (flat star,
        # per-leaf streaming star, or the two-level hierarchical round).
        tags = sync_step_tags(sync_step)

        def tag(name, default=None):
            v = tags.get(name)
            return default if v is None else v

        if reducer is None:
            reducer = tag("reducer")
        self.reducer = get_reducer(
            reducer if reducer is not None else tcfg.reducer,
            quant_bits=tcfg.quant_bits, topk_frac=tcfg.topk_frac)
        topo_spec = getattr(tcfg, "topology", "star")
        stream_hier_specs = ("streaming-hier", "hier-streaming",
                             "streaming-hierarchical")
        hier_spec = (topo_spec in ("hier", "hierarchical", "pods")
                     or topo_spec in stream_hier_specs)
        # a sync_step built with build_sync_step(streaming=True) implies the
        # per-leaf round even when the config says plain "star"
        self.streaming = (topo_spec in ("streaming", "streaming-star",
                                        "stream")
                          or topo_spec in stream_hier_specs
                          or bool(tag("streaming", False)))
        # ... and a hierarchical-tagged sync_step implies the two-level
        # round the same way. cfg n_pods=1 is the flat degenerate case
        # (no inter-pod link exists; build_sync_step emits the flat round).
        # streaming and hierarchical compose: the per-leaf two-level round
        # (Hierarchical(streaming=True)) prices like the blocking one.
        self.hierarchical = bool(tag("hierarchical", False)) or (
            hier_spec and getattr(tcfg, "n_pods", 2) > 1)
        if self.hierarchical:
            if not tag("hierarchical", False):
                # cfg promises a two-level round but the step transmits a
                # flat average: pricing Hierarchical would ledger bytes
                # the collectives never move.
                raise ValueError(
                    f"topology={tcfg.topology!r} needs a two-level sync "
                    f"step: build it with local_sgd.build_sync_step("
                    f"reducer, hierarchical=True, n_pods={tcfg.n_pods}, "
                    f"inter_reducer={tcfg.inter_reducer!r})")
            n_pods = tag("n_pods")
            if hier_spec and n_pods != tcfg.n_pods:
                raise ValueError(
                    f"sync_step reduces over {n_pods} pods but the config "
                    f"says n_pods={tcfg.n_pods}; the ledger would price a "
                    f"different topology than the round executes")
            self.n_pods = n_pods
            self.inter_reducer = get_reducer(
                tag("inter_reducer", getattr(tcfg, "inter_reducer", "int8")),
                quant_bits=tcfg.quant_bits, topk_frac=tcfg.topk_frac)
            cfg_inter = get_reducer(getattr(tcfg, "inter_reducer", "int8"),
                                    quant_bits=tcfg.quant_bits,
                                    topk_frac=tcfg.topk_frac)
            if hier_spec and tag("inter_reducer") is not None \
                    and self.inter_reducer.name != cfg_inter.name:
                # same contract as the n_pods check: cfg-derived reports
                # (comm_summary_for) and the executed ledger must price
                # the same WAN hop
                raise ValueError(
                    f"sync_step compresses the inter-pod hop with "
                    f"{self.inter_reducer.name!r} but the config says "
                    f"inter_reducer={tcfg.inter_reducer!r}; the ledger "
                    f"would price a different round than the one executed")
        elif topo_spec not in (None, "star", "flat", "streaming",
                               "streaming-star", "stream") and not hier_spec:
            raise ValueError(
                f"unknown topology spec for StagewiseDriver: "
                f"{tcfg.topology!r} (expected star/streaming/hierarchical/"
                f"streaming-hier)")
        self.net = NetworkModel(
            latency_s=tcfg.comm_latency_s,
            bandwidth_gbps=tcfg.comm_bandwidth_gbps,
            count_downlink=getattr(tcfg, "count_downlink", False))
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
        # trace-span attributes of one sync round — derived from the same
        # tags the ledger prices, so trace and ledger agree by construction
        self.span_attrs = {"reducer": self.reducer.name,
                           "streaming": self.streaming,
                           "hierarchical": self.hierarchical}
        if self.hierarchical:
            self.span_attrs.update(n_pods=self.n_pods,
                                   inter_reducer=self.inter_reducer.name)

    def build_topology(self):
        """The priced Topology of one sync round — exactly the round the
        tagged sync_step executes. Streaming rounds price identically to
        Star (same bytes, same serial α–β time) but additionally carry
        the per-leaf ledger; hierarchical rounds price per hop
        (calibrated ICI intra-pod, the config's α–β link inter-pod).
        Also what ``--profile`` uses to price one sync step."""
        if self.hierarchical:
            return Hierarchical(n_pods=self.n_pods, intra=self.reducer,
                                inter=self.inter_reducer,
                                intra_net=link_model("ici"),
                                inter_net=self.net,
                                streaming=self.streaming)
        topo_cls = StreamingStar if self.streaming else Star
        return topo_cls(reducer=self.reducer, network=self.net)

    def run(self, state: dict, batches, max_iters: Optional[int] = None,
            tracer=None, series=None) -> DriverState:
        ds = DriverState(state=state)
        # a fresh Engine per run: its report is the run's comm ledger,
        # priced on exactly the topology the sync_step executes —
        # modeled and executed bytes cannot diverge.
        engine = Engine(self.algorithm, self.tcfg,
                        topology=self.build_topology(),
                        tracer=tracer, series=series)
        ds = engine.run(DriverBackend(self, ds, batches, max_iters))
        log.info("comm_summary", reducer=self.reducer.name,
                 rounds=ds.rounds_total, comm_bytes=ds.comm_bytes_total,
                 comm_time_s=ds.comm_time_s)
        return ds
