"""N-client Local-SGD simulator — the vmapped execution backend.

This is the engine behind the paper-fidelity convergence experiments
(Figures 1–4, Tables 1–2): N client replicas live on a stacked leading axis,
local steps are vmapped (no communication), and a communication round is a
``repro.engine`` Topology reduction over the leading axis — a Star of the
configured ``repro.comm`` reducer by default (DenseMean is bit-exact
Algorithm 1 semantics), or a Hierarchical pod topology composing a dense
intra-pod average with a compressed inter-pod round.

Since the engine refactor this module is a *backend*: ``run()`` resolves
``cfg.algo`` through ``repro.engine.get_algorithm`` and hands a
``VmapSimulatorBackend`` to ``Engine.run`` — the SyncPolicy owns the stage
stream, the LocalUpdate owns the batch rule (large-batch / growing-batch
baselines included), and the same Engine drives the distributed
``StagewiseDriver``. The historical signature and the DenseMean trajectory
are preserved bit-for-bit (regression-pinned in tests/test_engine.py).

Algorithm names accepted by ``run`` are whatever the registry knows:
  sync, lb, crpsgd, local, stl_sc, stl_nc1, stl_nc2 (see repro.engine).
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Callable, List, Optional

import jax
import jax.numpy as jnp

from repro.comm import get_reducer
from repro.configs.base import TrainConfig
from repro.core.prox import prox_loss
from repro.core.round import end_round
from repro.engine.engine import Engine, StageStatus
from repro.obs.trace import CAT_COMM, CAT_COMPUTE
from repro.utils.tree import tree_broadcast_leading, tree_mean_leading, tree_zeros_like

# fold_in salt deriving the reducer's rng from the round rng without
# consuming it — keeps the local-step rng stream (and thus the DenseMean
# trajectory) bit-identical to the pre-comm-subsystem dense path.
_COMM_SALT = 0x5EED


@dataclass
class Record:
    round: int      # communication rounds so far
    iteration: int  # total iterations so far
    value: float    # eval_fn(averaged params)


def _sample_batch(data, rng, batch: int):
    """data: client-local dict of arrays with leading dim n. Uniform minibatch."""
    n = jax.tree.leaves(data)[0].shape[0]
    idx = jax.random.randint(rng, (batch,), 0, n)
    return jax.tree.map(lambda a: a[idx], data)


def make_batch_weights(batch: int, grow: float, b0: int, max_batch: int):
    """Per-example weight rule shared by every execution path.

    grow ≤ 1: uniform 1/batch. grow > 1 (CR-PSGD): bt = min(max, b0·grow^t)
    realised as a masked fixed-size buffer so compiled steps stay
    shape-stable.
    """

    def batch_weights(t):
        if grow <= 1.0:
            return jnp.ones((batch,), jnp.float32) / batch
        bt = jnp.minimum(float(max_batch), float(b0) * grow ** t)
        bt = jnp.clip(jnp.round(bt), 1, batch)
        mask = (jnp.arange(batch) < bt).astype(jnp.float32)
        return mask / bt

    return batch_weights


def client_sgd_step(loss_fn, batch: int, momentum: float,
                    p, m, d, rng, center, w, eta_t):
    """One client's minibatch SGD(+momentum) step.

    The single copy of the inner update math — the vmapped round, the
    masked-dropout round, the adaptive probe step and the async client job
    (repro.runtime) all call this, so the execution paths cannot drift.
    """
    b = _sample_batch(d, rng, batch)
    g = jax.grad(lambda q: loss_fn(q, b, center, w))(p)
    m2 = jax.tree.map(lambda mm, gg: momentum * mm + gg, m, g)
    p2 = jax.tree.map(lambda pp, mm: pp - eta_t * mm, p, m2)
    return p2, m2


def make_round_fn(loss_fn, *, k: int, batch: int, momentum: float,
                  lr_alpha: float, grow: float, b0: int, max_batch: int,
                  reducer=None, masked: bool = False):
    """One communication round = k vmapped local steps + 1 reduced average.

    Returned fn: (carry, rng, data, center, eta) -> carry where
    carry = (params_stacked, momentum_stacked, t_global_f32, comm_state).
    loss_fn(params, batch, center, weights) -> scalar.

    ``reducer`` (default DenseMean) owns the parameter average — any object
    with the reduce/init_state protocol works, i.e. a ``comm.Reducer`` or a
    ``engine.Topology``; its residual/error-feedback state rides in the
    carry. Momentum is always dense-averaged: it never leaves the client in
    a real deployment, the average only mirrors Alg. 1's replica-consensus
    bookkeeping.

    ``masked=True`` returns the dropout-aware variant (used by
    ``repro.runtime.EventBackend``) taking a trailing (N,) bool mask:
    inactive clients are frozen for the round's k local steps — they missed
    their compute window — but the reduce still spans all N replicas, so a
    dropped client contributes a zero delta (plus, under error-feedback
    reducers, whatever residual it already carried, which keeps the EF
    state convergent) and compressed/hierarchical topologies compose with
    partial participation unchanged. One round body serves both variants;
    the unmasked trace is bit-identical to the historical dense path.
    """
    reducer = reducer if reducer is not None else get_reducer(None)
    batch_weights = make_batch_weights(batch, grow, b0, max_batch)

    def round_body(carry, rng_r, data, center, eta, mask):
        N = jax.tree.leaves(carry[0])[0].shape[0]

        def local_step(c, rng_t):
            params, mom, t = c
            eta_t = eta / (1.0 + lr_alpha * t)
            w = batch_weights(t)

            def client(p, m, d, rng, active=None):
                p2, m2 = client_sgd_step(loss_fn, batch, momentum, p, m, d,
                                         rng, center, w, eta_t)
                if active is None:
                    return p2, m2
                freeze = lambda new, old: jax.tree.map(
                    lambda a, o: jnp.where(active, a, o), new, old)
                return freeze(p2, p), freeze(m2, m)

            rngs = jax.random.split(rng_t, N)
            if mask is None:
                params, mom = jax.vmap(
                    lambda p, m, d, rng: client(p, m, d, rng)
                )(params, mom, data, rngs)
            else:
                params, mom = jax.vmap(client)(params, mom, data, rngs, mask)
            return (params, mom, t + 1.0), None

        params, mom, t, comm = carry
        (params, mom, t), _ = jax.lax.scan(
            local_step, (params, mom, t), jax.random.split(rng_r, k))
        consensus, comm = reducer.reduce(
            params, comm, jax.random.fold_in(rng_r, _COMM_SALT))
        params, mom = end_round(consensus, mom, N)
        return (params, mom, t, comm)

    if masked:
        return round_body
    return lambda carry, rng_r, data, center, eta: round_body(
        carry, rng_r, data, center, eta, None)


def make_local_step_fn(loss_fn, *, batch: int, momentum: float,
                       lr_alpha: float, grow: float, b0: int, max_batch: int):
    """One vmapped local step for all N clients, *no* communication.

    The probe-granularity sibling of ``make_round_fn`` (same client math,
    via ``client_sgd_step``), used by the divergence-triggered
    ``AdaptivePeriod`` policy where the backend decides after every step
    whether to run the round.
    """
    batch_weights = make_batch_weights(batch, grow, b0, max_batch)

    def step_fn(params, mom, t, rng_t, data, center, eta):
        N = jax.tree.leaves(params)[0].shape[0]
        eta_t = eta / (1.0 + lr_alpha * t)
        w = batch_weights(t)
        rngs = jax.random.split(rng_t, N)
        params, mom = jax.vmap(
            lambda p, m, d, rng: client_sgd_step(
                loss_fn, batch, momentum, p, m, d, rng, center, w, eta_t)
        )(params, mom, data, rngs)
        return params, mom, t + 1.0

    return step_fn


def replica_divergence(stacked):
    """Relative replica spread: Σ_leaves mean_i ‖x_i − x̄‖² / (‖x̄‖² + ε).

    The probe the AdaptivePeriod policy thresholds — zero right after a
    round (replicas identical), growing with local drift.
    """
    mean = tree_mean_leading(stacked)
    num = 0.0
    den = 0.0
    for x, m in zip(jax.tree.leaves(stacked), jax.tree.leaves(mean)):
        d = x.astype(jnp.float32) - m[None].astype(jnp.float32)
        num += jnp.mean(jnp.sum(d * d, axis=tuple(range(1, d.ndim))))
        den += jnp.sum(m.astype(jnp.float32) ** 2)
    return num / (den + 1e-12)


class VmapSimulatorBackend:
    """Engine backend: N vmapped client replicas on one host.

    Owns the chunked-scan execution of each stage (``chunk_rounds``
    communication rounds per jit call, per-round eval inside the scan), the
    (round, objective) history, and the target/max_rounds early exit.
    Compiled chunk functions are cached per (k, batch) — stages that only
    change η reuse the compilation (η is a traced operand).
    """

    def __init__(self, loss_fn: Callable, init_params, client_data,
                 eval_fn: Callable, *, eval_every: int = 1,
                 max_rounds: Optional[int] = None,
                 target: Optional[float] = None, lr_alpha: float = 0.0,
                 chunk_rounds: int = 32):
        self.loss_fn = loss_fn
        self.init_params = init_params
        self.client_data = client_data
        self.eval_fn = eval_fn
        self.eval_every = eval_every
        self.max_rounds = max_rounds
        self.target = target
        self.lr_alpha = lr_alpha
        self.chunk_rounds = chunk_rounds

    def setup(self, engine: Engine):
        cfg = engine.cfg
        algo = engine.algorithm
        N = jax.tree.leaves(self.client_data)[0].shape[0]
        self.use_prox = algo.uses_center(cfg)
        ploss = prox_loss(self.loss_fn, algo.gamma_inv(cfg))
        self.wloss = algo.local_update.make_loss(ploss)
        self.batch = algo.local_update.round_batch(cfg)
        self.grow = algo.local_update.growth(cfg)

        self.params = tree_broadcast_leading(self.init_params, N)
        self.mom = tree_zeros_like(self.params)
        self.comm_state = engine.topology.init_state(self.params)
        self.rng = jax.random.key(cfg.seed)
        self.history: List[Record] = [
            Record(0, 0, float(self.eval_fn(self.init_params)))]
        self.rounds_done = 0
        self.iters_done = 0
        self.t_global = 0.0
        self._chunk_cache = {}
        engine.set_cost_basis(self.init_params, N)

    def _chunk_fn(self, engine: Engine, k: int, b: int):
        key = (k, b)
        if key not in self._chunk_cache:
            cfg = engine.cfg
            round_fn = make_round_fn(
                self.wloss, k=k, batch=b, momentum=cfg.momentum,
                lr_alpha=self.lr_alpha, grow=self.grow,
                b0=cfg.batch_per_client, max_batch=cfg.max_batch,
                reducer=engine.topology)
            eval_fn = self.eval_fn

            @partial(jax.jit, static_argnames=("n",))
            def chunk_fn(carry, rng_c, data, ctr, eta, n):
                def body(c, rng_r):
                    c = round_fn(c, rng_r, data, ctr, eta)
                    return c, eval_fn(tree_mean_leading(c[0]))
                return jax.lax.scan(body, carry, jax.random.split(rng_c, n))

            self._chunk_cache[key] = chunk_fn
        return self._chunk_cache[key]

    def _sample_round_masks(self, n: int):
        """Per-(round, client) participation masks for the next n rounds.

        None (the default) means full participation and the unmasked chunk
        function; ``repro.runtime.EventBackend`` overrides this (and
        ``_chunk_fn``) to thread dropout masks through the rounds.
        """
        return None

    def run_stage(self, stage, engine: Engine) -> StageStatus:
        policy = engine.algorithm.sync_policy
        if getattr(policy, "asynchronous", False):
            raise ValueError(
                "asynchronous policies (barrier-free rounds) need the "
                "event-driven backend: use repro.runtime.EventBackend / "
                "runtime.run instead of the vmapped simulator")
        if getattr(policy, "adaptive", False):
            return self._run_stage_adaptive(stage, engine)
        k = stage.k
        chunk_fn = self._chunk_fn(engine, k, self.batch)
        # Non-prox algorithms have no center: pass None (an empty pytree) so
        # nothing downstream can silently consume a stale parameter snapshot.
        center = tree_mean_leading(self.params) if self.use_prox else None

        status = StageStatus()
        n_rounds = -(-stage.T // k)  # ceil
        carry = (self.params, self.mom,
                 jnp.asarray(self.t_global, jnp.float32), self.comm_state)
        done_in_stage = 0
        while done_in_stage < n_rounds:
            n = min(self.chunk_rounds, n_rounds - done_in_stage)
            self.rng, sub = jax.random.split(self.rng)
            masks = self._sample_round_masks(n)
            # one wall span per jit chunk — the host-visible unit of work
            # (n fused rounds of k local steps + reduce each)
            with engine.tracer.span("local_steps", cat=CAT_COMPUTE,
                                    track="simulator",
                                    attrs={"s": stage.s, "rounds": n,
                                           "k": k, "eta": stage.eta}):
                if masks is None:
                    carry, vals = chunk_fn(carry, sub, self.client_data,
                                           center, stage.eta, n)
                else:
                    carry, vals = chunk_fn(carry, sub, self.client_data,
                                           center, stage.eta,
                                           jnp.asarray(masks), n)
                vals = list(map(float, vals))
            hit = None
            for j, v in enumerate(vals):
                rd = self.rounds_done + j + 1
                at_target = self.target is not None and v <= self.target
                if rd % self.eval_every == 0 \
                        or (done_in_stage + j + 1) == n_rounds \
                        or (at_target and hit is None):
                    self.history.append(
                        Record(rd, self.iters_done + (j + 1) * k, v))
                if at_target and hit is None:
                    hit = rd
            self.rounds_done += n
            self.iters_done += n * k
            done_in_stage += n
            status.rounds += n
            status.iters += n * k
            if hit is not None:
                status.stop = True
                break
            if self.max_rounds is not None \
                    and self.rounds_done >= self.max_rounds:
                status.stop = True
                break
        self.params, self.mom, tg, self.comm_state = carry
        self.t_global = float(tg)
        # steps-per-round breakdown for event-clock overlays (EventBackend)
        self._last_round_steps = [k] * status.rounds
        engine.metrics.gauge(
            "train.stage_objective", unit="objective",
            help="eval_fn(averaged params) at stage end").set(
                self.history[-1].value, stage=stage.s)
        return status

    # -- divergence-triggered periods (AdaptivePeriod) ----------------------

    def _adaptive_fns(self, engine: Engine, b: int):
        key = ("adaptive", b)
        if key not in self._chunk_cache:
            cfg = engine.cfg
            step = make_local_step_fn(
                self.wloss, batch=b, momentum=cfg.momentum,
                lr_alpha=self.lr_alpha, grow=self.grow,
                b0=cfg.batch_per_client, max_batch=cfg.max_batch)
            topo = engine.topology

            @jax.jit
            def step_fn(params, mom, t, rng, data, center, eta):
                params, mom, t = step(params, mom, t, rng, data, center, eta)
                return params, mom, t, replica_divergence(params)

            @jax.jit
            def sync_fn(params, mom, comm, rng):
                N = jax.tree.leaves(params)[0].shape[0]
                consensus, comm = topo.reduce(params, comm, rng)
                params, mom = end_round(consensus, mom, N)
                return params, mom, comm, consensus

            self._chunk_cache[key] = (step_fn, sync_fn)
        return self._chunk_cache[key]

    def _run_stage_adaptive(self, stage, engine: Engine) -> StageStatus:
        """Probe-and-trigger loop: one vmapped local step at a time; the
        round runs when replica divergence crosses the policy threshold, the
        stage's k-cap is hit, or the stage ends."""
        policy = engine.algorithm.sync_policy
        step_fn, sync_fn = self._adaptive_fns(engine, self.batch)
        center = tree_mean_leading(self.params) if self.use_prox else None

        status = StageStatus()
        self._last_round_steps = []
        params, mom = self.params, self.mom
        t = jnp.asarray(self.t_global, jnp.float32)
        since_sync = 0
        for it in range(stage.T):
            self.rng, sub = jax.random.split(self.rng)
            params, mom, t, div = step_fn(params, mom, t, sub,
                                          self.client_data, center, stage.eta)
            since_sync += 1
            self.iters_done += 1
            status.iters += 1
            last = it == stage.T - 1
            if not (last or since_sync >= stage.k
                    or float(div) >= policy.threshold):
                continue
            with engine.tracer.span("reduce", cat=CAT_COMM,
                                    track="simulator",
                                    attrs={"s": stage.s,
                                           "steps": since_sync}):
                params, mom, self.comm_state, consensus = sync_fn(
                    params, mom, self.comm_state,
                    jax.random.fold_in(sub, _COMM_SALT))
            status.rounds += 1
            self.rounds_done += 1
            self._last_round_steps.append(since_sync)
            since_sync = 0
            v = float(self.eval_fn(consensus))
            at_target = self.target is not None and v <= self.target
            if self.rounds_done % self.eval_every == 0 or last or at_target:
                self.history.append(Record(self.rounds_done, self.iters_done,
                                           v))
            if at_target or (self.max_rounds is not None
                             and self.rounds_done >= self.max_rounds):
                status.stop = True
                break
        self.params, self.mom = params, mom
        self.t_global = float(t)
        engine.metrics.gauge(
            "train.stage_objective", unit="objective",
            help="eval_fn(averaged params) at stage end").set(
                self.history[-1].value, stage=stage.s)
        return status

    def finish(self, engine: Engine) -> List[Record]:
        return self.history


def run(loss_fn: Callable, init_params, client_data, cfg: TrainConfig,
        eval_fn: Callable, *, eval_every: int = 1, max_rounds: Optional[int] = None,
        target: Optional[float] = None, lr_alpha: float = 0.0,
        chunk_rounds: int = 32, reducer=None, topology=None,
        tracer=None) -> List[Record]:
    """Run ``cfg.algo`` and return the (comm-round, objective) trace.

    loss_fn(params, batch) -> scalar (per-client minibatch loss).
    client_data: pytree with leading client axis N on every leaf.
    eval_fn(params) -> scalar on the *averaged* model.
    ``chunk_rounds`` communication rounds are scanned inside one jit call
    (with per-round eval), so the Python loop runs ~chunk_rounds× less often.
    ``reducer`` — a comm.Reducer or spec string for the communication round;
    defaults to ``cfg.reducer`` (DenseMean unless configured otherwise),
    which is bit-exact with the historical dense path.
    ``topology`` — an engine.Topology or spec string ("star" | "hier");
    defaults to ``cfg.topology`` with ``reducer`` on the first hop.
    ``tracer`` — an ``obs.Tracer`` to record wall/modeled span timelines
    into (None = disabled, zero overhead).
    """
    engine = Engine(cfg.algo, cfg, topology=topology, reducer=reducer,
                    tracer=tracer)
    backend = VmapSimulatorBackend(
        loss_fn, init_params, client_data, eval_fn, eval_every=eval_every,
        max_rounds=max_rounds, target=target, lr_alpha=lr_alpha,
        chunk_rounds=chunk_rounds)
    return engine.run(backend)


def rounds_to_target(history: List[Record], target: float) -> Optional[int]:
    for rec in history:
        if rec.value <= target:
            return rec.round
    return None
