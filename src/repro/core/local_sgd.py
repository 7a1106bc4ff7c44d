"""Distributed Local-SGD step builders (pjit + client replicas on a mesh axis).

The paper's clients map to a mesh axis (docs/topologies.md):

  * ``train_step_local`` — every client takes one SGD step on its own replica.
    Parameters carry a leading client axis sharded on ``client_axis``; the
    step is ``jax.vmap(per_client_step, spmd_axis_name=client_axis)`` so XLA
    emits **zero collectives on the client axis** (tensor-parallel collectives
    on ``model`` remain). Executed k_s times per round.

  * ``sync_step`` — Algorithm 1 line 5: the parameter-averaging round,
    the ``engine.Topology`` that ``engine.get_topology`` resolves (flat
    star, per-leaf streaming star, or the two-level pod round), the same
    reduce the simulator executes. The dense star is one all-reduce of
    params (+ optimizer moments) over the client axis; with one replica
    per device, bfloat16 params take a float32 reduce-scatter and an
    all-gather in their own dtype instead (``_gathered_mean``). The
    two-level round (``client_axis=("pod", "data")`` + ``inter_reducer``)
    reduces intra-pod over ``data`` and inter-pod over ``pod``.

  * hierarchical pod-client mode (``client_axis="pod"``): grads are
    additionally all-reduced over ``data`` *inside* the local step (SyncSGD
    within a pod over fast ICI), while the stagewise schedule governs only
    the expensive inter-pod parameter average. This is the beyond-paper
    deployment mode.

All builders return *lowerable* jitted callables — the multi-pod dry-run
compiles exactly these.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import ArchConfig
from repro.core.round import end_round
from repro.engine.topology import Star, Topology, get_topology
from repro.kernels.flash_attention.causal import kernel_mesh
from repro.kernels.replica_mean import kernel_view, replica_mean
from repro.models import transformer as TF
from repro.obs.lowered import lowering_counter
from repro.optim import make_optimizer
from repro.sharding import param_specs, scatter_dim
from repro.sharding.rules import cache_specs
from repro.utils.tree import tree_broadcast_leading


# ---------------------------------------------------------------------------
# Loss
# ---------------------------------------------------------------------------

def lm_loss(params, cfg: ArchConfig, batch):
    """Next-token CE. batch: {"tokens","labels": (B,S)} [+ "frontend"]."""
    logits, aux = TF.forward(params, cfg, batch["tokens"], batch.get("frontend"))
    S = batch["labels"].shape[1]
    logits = logits[:, -S:, :]  # drop frontend positions
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    nll = -jnp.take_along_axis(logp, batch["labels"][..., None], axis=-1)[..., 0]
    return jnp.mean(nll) + aux


# ---------------------------------------------------------------------------
# Step builders
# ---------------------------------------------------------------------------

def batch_spec(cfg: ArchConfig, client_axis: Optional[str], extra_data_axis: bool):
    """PartitionSpec tree for the training batch.

    Leading batch dim carries the client axis and (hierarchical mode) the
    intra-pod data axis.
    """
    axes = []
    if client_axis:
        # multi-axis client grids (("pod", "data") on a multi-pod mesh)
        # shard the one leading client dim over all their mesh axes
        if isinstance(client_axis, (tuple, list)):
            axes.extend(client_axis)
        else:
            axes.append(client_axis)
    if extra_data_axis:
        axes.append("data")
    lead = tuple(axes) if axes else None
    spec = {"tokens": P(lead, None), "labels": P(lead, None)}
    if cfg.frontend:
        spec["frontend"] = P(lead, None, None)
    return spec


def _client_sharded(tree, mesh, client_axis):
    """Constrain the leading (client) dim of every leaf to ``client_axis``
    on ``mesh``, other dims left to XLA. Without it the broadcast
    consensus comes out replicated: every device would hold every
    client's replica."""
    if mesh is None:
        return tree

    def one(x):
        spec = P(client_axis, *([P.UNCONSTRAINED] * (x.ndim - 1)))
        return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))

    return jax.tree.map(one, tree)


_sync_lowered = lowering_counter(
    "sync.lowered",
    help="averaging rounds lowered, by how the mean crosses the client axis")


def _one_replica_per_device(mesh, client_axis, n: int) -> bool:
    """Whether the client axis spans ``n`` > 1 devices, one replica each,
    and no other mesh axis splits a leaf."""
    if mesh is None or n < 2:
        return False
    axes = (client_axis if isinstance(client_axis, (tuple, list))
            else (client_axis,))
    return math.prod(mesh.shape[a] for a in axes) == n == mesh.size


def _gathered_mean(x, mesh, client_axis, d: int):
    """``_client_sharded(tree_broadcast_leading(tree_mean_leading(x), n))``
    for a leaf narrower than float32 with one replica a device: the
    replica widened to float32 and reduce-scattered over ``client_axis``
    along ``d``, its 1/n shard divided by n and cast to the leaf's dtype,
    then all-gathered in that dtype. Each element is the same float32 sum
    of the same n values, rounded to the leaf's dtype once, as the
    all-reduce gives it, and the gather moves half the bytes.

    The gathered consensus is written out by a rounding to the leaf's own
    format, which changes no value: XLA on TPU copies a collective's result
    that is a program output, and then copies the donated input too, so
    the round would make two more passes over those leaves.
    """
    n = x.shape[0]
    f = jnp.finfo(x.dtype)

    def body(replica):
        with jax.named_scope("reduce"):
            part = jax.lax.psum_scatter(
                replica[0].astype(jnp.float32), client_axis,
                scatter_dimension=d, tiled=True)
            part = (part / n).astype(x.dtype)
        with jax.named_scope("broadcast"):
            full = jax.lax.all_gather(part, client_axis, axis=d, tiled=True)
            return jax.lax.reduce_precision(
                full, exponent_bits=f.nexp, mantissa_bits=f.nmant)[None]

    return jax.shard_map(body, mesh=mesh, in_specs=P(client_axis),
                         out_specs=P(client_axis))(x)


def _gather_narrow(replicas, consensus, mesh, client_axis):
    """``consensus`` with each leaf narrower than float32 that has a
    ``scatter_dim`` replaced by its ``_gathered_mean``; XLA drops the
    all-reduce those leaves no longer use. A float32 leaf keeps the
    all-reduce: on a v5e 2x2 a float32 reduce-scatter and all-gather take
    about 15% longer than the all-reduce of the same leaf."""
    def leaf(x, mean):
        narrow = (jnp.issubdtype(x.dtype, jnp.floating)
                  and x.dtype.itemsize < 4)
        d = scatter_dim(x.shape[1:], x.shape[0]) if narrow else None
        return mean if d is None else _gathered_mean(x, mesh, client_axis, d)

    return jax.tree.map(leaf, replicas, consensus)


def _colocated(mesh) -> bool:
    """Whether every replica of a leaf sits on one device: no mesh, or a
    mesh of one device."""
    return mesh is None or mesh.size == 1


def _average_in_place(replicas, consensus):
    """``consensus`` with each leaf that has a ``kernel_view`` replaced, on
    TPU, by ``replica_mean`` of its replicas: one in-place pass for the
    four of ``jnp.mean`` + ``broadcast_to``, the same float32 sum divided
    by n and rounded once. Every other platform lowers ``consensus`` as it
    is, and XLA drops whichever of the two a program does not use."""
    def leaf(x, mean):
        if kernel_view(x.shape, x.dtype) is None:
            return mean
        return jax.lax.platform_dependent(
            x, mean, tpu=lambda x, _: replica_mean(x),
            default=lambda _, mean: mean)

    return jax.tree.map(leaf, replicas, consensus)


def build_sync_step(reducer=None, *, base_seed: int = 0,
                    streaming: bool = False, hierarchical: bool = False,
                    n_pods: int = 2, inter_reducer="int8", mesh=None,
                    client_axis="data"):
    """Reducer-aware Algorithm 1 line 5: the parameter-averaging round.

    Returns ``sync_step(state) -> state``, which runs one
    ``engine.Topology`` and carries it as ``sync_step.topology``:
    ``get_topology`` resolves it from ``reducer`` (the uplink reducer,
    or the intra-pod one), ``streaming`` (the per-leaf round XLA can
    overlap with compute), ``hierarchical`` (``n_pods`` pods of
    contiguous clients and an ``inter_reducer`` hop between them; one
    pod is the flat star); a ``Topology`` passed as ``reducer`` is run
    as it is. The round reduces the params with the topology, folding
    ``state["step"]`` into ``base_seed`` for its rng so the step stays
    pure, then broadcasts the consensus and the averaged optimizer
    moments (``core.round.end_round``); the moments never cross the
    network in a real deployment. A stateful round (compressed reducers'
    error feedback) keeps its state in ``state["comm"]``, created on the
    first round; a stateless one leaves the state tree's keys as they are.

    ``mesh`` (with the ``client_axis`` the replicas are sharded over)
    keeps the round's output params and moments split over the client
    axis, as its input was. Where a dense blocking star runs with one
    replica per device and no other axis splits a leaf, its narrow
    leaves are averaged through ``_gathered_mean``; where it runs with all
    replicas on one device (``mesh`` None or of one device), its leaves
    are averaged in place through ``_average_in_place``. Each round
    lowered counts once under
    ``sync.lowered{path=scatter_gather|colocated|all_reduce}``.
    """
    topology = get_topology(
        reducer if isinstance(reducer, Topology)
        else ("streaming-" if streaming else "")
        + ("hier" if hierarchical else "star"),
        reducer=reducer, n_pods=n_pods, inter_reducer=inter_reducer)
    dense_star = type(topology) is Star and not topology.stateful

    def sync_step(state):
        # named scopes "reduce" and "broadcast" name the round's two halves
        # in a profile's op metadata
        n = jax.tree.leaves(state["params"])[0].shape[0]
        gather = dense_star and _one_replica_per_device(mesh, client_axis, n)
        colocated = dense_star and _colocated(mesh)
        rng = jax.random.fold_in(jax.random.key(base_seed), state["step"])
        out = dict(state)
        with jax.named_scope("reduce"):
            comm = state.get("comm") if topology.stateful else None
            if topology.stateful and comm is None:
                comm = topology.init_state(state["params"])
            consensus, comm = topology.reduce(state["params"], comm, rng)
        if topology.stateful:
            out["comm"] = comm
        out["params"], out["opt"] = end_round(consensus, state["opt"], n)
        with jax.named_scope("broadcast"):
            out.update(_client_sharded({"params": out["params"],
                                        "opt": out["opt"]}, mesh,
                                       client_axis))
        if gather:
            out.update({k: _gather_narrow(state[k], out[k], mesh, client_axis)
                        for k in ("params", "opt")})
        if colocated:
            out.update({k: _average_in_place(state[k], out[k])
                        for k in ("params", "opt")})
        out["step"] = _sync_lowered(
            state["step"], path="scatter_gather" if gather
            else "colocated" if colocated else "all_reduce")
        return out

    # the driver prices the topology the round runs
    sync_step.topology = topology
    return sync_step


def sync_step_topology(sync_step) -> Optional[Topology]:
    """The ``Topology`` a ``build_sync_step`` round carries, read through
    any stack of wrappers that chain ``__wrapped__`` (``jax.jit``,
    ``functools.wraps`` decorators like ``obs.ProfileSession.wrap``);
    ``None`` for a step that carries none."""
    fn = sync_step
    for _ in range(8):   # walk the full wrapper chain (cycle-safe)
        if fn is None:
            return None
        topology = getattr(fn, "topology", None)
        if topology is not None:
            return topology
        fn = getattr(fn, "__wrapped__", None)
    return None


def build_train_steps(cfg: ArchConfig, mesh, *, client_axis: str = "data",
                      optimizer: str = "sgd", momentum: float = 0.0,
                      weight_decay: float = 0.0,
                      loss_fn: Optional[Callable] = None,
                      microbatch: int = 1,
                      sync_grads: bool = False,
                      reducer=None,
                      streaming: bool = False,
                      inter_reducer=None):
    """Returns (train_step_local, sync_step, specs) for the given mesh.

    train_step_local(state, batch, eta) -> (state, metrics)
        state = {"params": (C, ...), "opt": (C, ...), "step": scalar}
    sync_step(state) -> state   (client-axis parameter average; built by
        ``build_sync_step(reducer, streaming=streaming)`` — pass ``reducer``
        for a compressed round (or an ``engine.Topology`` to run as is),
        default dense; ``streaming=True`` for the per-leaf reduce XLA can
        overlap with compute)

    ``microbatch`` > 1 splits each client's batch into that many
    gradient-accumulation slices (scan), dividing activation memory.
    In hierarchical mode (client_axis="pod") the per-client gradient is
    additionally pmean'd over "data" inside the local step.

    ``inter_reducer`` (with a client axis spanning "pod", e.g.
    ``client_axis=("pod", "data")`` on a multi-pod mesh) selects the
    *two-level* sync round: the paper's clients live on the pod×data grid
    and every sync runs a dense intra-pod reduce over ``data`` followed by
    an ``inter_reducer`` round over the ``pod`` axis (int8-EF WAN by
    default) — ``build_sync_step(hierarchical=True)`` with ``n_pods``
    taken from the mesh. ``None`` (default) keeps the historical flat
    client-axis average.
    """
    loss_fn = loss_fn or lm_loss
    hierarchical = client_axis == "pod"
    two_level = inter_reducer is not None
    if two_level:
        axes = (client_axis if isinstance(client_axis, (tuple, list))
                else (client_axis,))
        if "pod" not in axes or "pod" not in mesh.axis_names:
            raise ValueError(
                f"inter_reducer={inter_reducer!r} requests the two-level "
                f"sync round, but client_axis={client_axis!r} on a mesh "
                f"with axes {tuple(mesh.axis_names)} has no 'pod' axis to "
                f"cross — use client_axis=('pod', 'data') on a multi-pod "
                f"mesh")
        n_pods = dict(zip(mesh.axis_names, mesh.devices.shape))["pod"]
    opt_init, opt_update = make_optimizer(optimizer, momentum, weight_decay)

    def scoped_loss(params, batch):
        # op metadata only: the forward's ops read loss/..., the backward's
        # transpose(jvp(loss))/... in a profile
        with jax.named_scope("loss"):
            return loss_fn(params, cfg, batch)

    def per_client_grad(params, batch):
        if microbatch == 1:
            return jax.value_and_grad(lambda p: scoped_loss(p, batch))(params)

        def slice_mb(x, i):
            mb = x.shape[0] // microbatch
            return jax.lax.dynamic_slice_in_dim(x, i * mb, mb, axis=0)

        def body(carry, i):
            loss_acc, g_acc = carry
            mb = jax.tree.map(lambda x: slice_mb(x, i), batch)
            loss, g = jax.value_and_grad(lambda p: scoped_loss(p, mb))(params)
            return (loss_acc + loss, jax.tree.map(jnp.add, g_acc, g)), None

        zero = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
        (loss, grads), _ = jax.lax.scan(
            body, (jnp.zeros((), jnp.float32), zero),
            jnp.arange(microbatch))
        inv = 1.0 / microbatch
        return loss * inv, jax.tree.map(lambda g: g * inv, grads)

    def per_client_step(params, opt_state, batch, eta):
        if hierarchical:
            # batch: (data_shards, per_shard, S). SyncSGD within the pod —
            # per-shard grads (vmapped over `data`) averaged over the leading
            # axis = the intra-pod gradient all-reduce over fast ICI.
            losses, grads = jax.vmap(
                lambda b: per_client_grad(params, b),
                spmd_axis_name="data")(batch)
            grads = jax.tree.map(lambda g: jnp.mean(g, axis=0), grads)
            loss = jnp.mean(losses)
        else:
            loss, grads = per_client_grad(params, batch)
        if sync_grads:
            # SyncSGD baseline: all-reduce grads over the client axis.
            grads = jax.lax.pmean(grads, axis_name="clients")
            loss = jax.lax.pmean(loss, axis_name="clients")
        with jax.named_scope("optimizer"):
            params, opt_state = opt_update(params, grads, opt_state, eta)
        return params, opt_state, loss

    vstep = jax.vmap(per_client_step, in_axes=(0, 0, 0, None),
                     out_axes=(0, 0, 0), spmd_axis_name=client_axis,
                     axis_name="clients")

    def train_step_local(state, batch, eta):
        with kernel_mesh(mesh):
            params, opt, loss = vstep(state["params"], state["opt"], batch,
                                      eta)
        # dict(state, ...) so extra keys (e.g. a compressed sync_step's
        # "comm" error-feedback residuals) survive the local step.
        return dict(state, params=params, opt=opt, step=state["step"] + 1), {
            "loss": jnp.mean(loss)}

    sync_step = (build_sync_step(reducer, streaming=streaming,
                                 hierarchical=True, n_pods=n_pods,
                                 inter_reducer=inter_reducer, mesh=mesh,
                                 client_axis=client_axis)
                 if two_level else
                 build_sync_step(reducer, streaming=streaming, mesh=mesh,
                                 client_axis=client_axis))

    return train_step_local, sync_step, per_client_step


def state_shardings(cfg: ArchConfig, mesh, params_shape, opt_shape,
                    client_axis: str = "data"):
    """NamedShardings for the training state pytree.

    Hierarchical mode (client_axis == 'pod') additionally FSDP-shards each
    replica over the intra-pod 'data' axis.
    """
    from repro.sharding.rules import feasible_specs

    fsdp = "data" if client_axis == "pod" else None
    pspecs = feasible_specs(
        param_specs(params_shape, client_axis=client_axis, fsdp_axis=fsdp),
        params_shape, mesh)
    ospecs = {"mu": pspecs} if "mu" in opt_shape else {
        k: (pspecs if k in ("m", "v") else P()) for k in opt_shape}
    to_sh = lambda tree: jax.tree.map(lambda s: NamedSharding(mesh, s), tree,
                                      is_leaf=lambda s: isinstance(s, P))
    return {"params": to_sh(pspecs), "opt": to_sh(ospecs),
            "step": NamedSharding(mesh, P())}


def init_sharded_state(rng, cfg: ArchConfig, n_clients: int, mesh,
                       optimizer: str = "sgd", client_axis="data"):
    """``init_state`` compiled straight into ``state_shardings`` on
    ``mesh``: each device materialises only the replicas it holds, so no
    replica set is first built whole on one device."""
    shape = init_state_shape(cfg, n_clients, optimizer)
    sh = state_shardings(cfg, mesh, shape["params"], shape["opt"],
                         client_axis=client_axis)
    return jax.jit(lambda k: init_state(k, cfg, n_clients, optimizer),
                   out_shardings=sh)(rng)


def init_state(rng, cfg: ArchConfig, n_clients: int, optimizer: str = "sgd"):
    """Materialised training state with client replicas, on the default
    device (``init_sharded_state`` places it on a mesh)."""
    opt_init, _ = make_optimizer(optimizer)
    params = TF.init_params(rng, cfg)
    opt = opt_init(params)
    return {
        "params": tree_broadcast_leading(params, n_clients),
        "opt": tree_broadcast_leading(opt, n_clients),
        "step": jnp.zeros((), jnp.int32),
    }


def init_state_shape(cfg: ArchConfig, n_clients: int, optimizer: str = "sgd"):
    """Shape-only state (ShapeDtypeStructs) for the dry-run."""
    return jax.eval_shape(
        lambda k: init_state(k, cfg, n_clients, optimizer), jax.random.key(0))
