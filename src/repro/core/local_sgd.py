"""Distributed Local-SGD step builders (pjit + client replicas on a mesh axis).

The paper's clients map to a mesh axis (DESIGN.md §2/§4):

  * ``train_step_local`` — every client takes one SGD step on its own replica.
    Parameters carry a leading client axis sharded on ``client_axis``; the
    step is ``jax.vmap(per_client_step, spmd_axis_name=client_axis)`` so XLA
    emits **zero collectives on the client axis** (tensor-parallel collectives
    on ``model`` remain). Executed k_s times per round.

  * ``sync_step`` — Algorithm 1 line 5: the parameter-averaging round. One
    all-reduce of params (+ optimizer moments) over the client axis; with
    one replica per device, bfloat16 params take a float32 reduce-scatter
    and an all-gather in their own dtype instead (``_gathered_mean``).

  * two-level sync (``client_axis=("pod", "data")`` + ``inter_reducer``):
    the paper's clients live on the pod×data grid and every sync runs the
    real hierarchical round — a dense intra-pod reduce over ``data``
    followed by a (typically compressed) inter-pod hop over ``pod`` — via
    ``build_sync_step(hierarchical=True)``, the same ``engine.Hierarchical``
    reduce the simulator executes (see docs/topologies.md).

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

from repro.comm import get_reducer
from repro.comm.reducer import DenseMean, reduce_streaming
from repro.configs.base import ArchConfig
from repro.kernels.flash_attention.causal import kernel_mesh
from repro.models import transformer as TF
from repro.obs.lowered import lowering_counter
from repro.optim import make_optimizer
from repro.sharding import param_specs, scatter_dim
from repro.sharding.rules import cache_specs
from repro.utils.tree import tree_broadcast_leading, tree_mean_leading


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


def build_sync_step(reducer=None, *, base_seed: int = 0,
                    streaming: bool = False, hierarchical: bool = False,
                    n_pods: int = 2, inter_reducer="int8", mesh=None,
                    client_axis="data"):
    """Reducer-aware Algorithm 1 line 5: the parameter-averaging round.

    Returns ``sync_step(state) -> state``. With the default DenseMean this is
    exactly the historical dense average (and leaves the state tree
    untouched). With a compressed reducer, each client's message is
    compressed with error feedback; the residual state rides in
    ``state["comm"]`` (created on first sync), and the reducer rng derives
    from ``state["step"]`` so the step stays a pure jittable function.
    Optimizer moments are always dense-averaged — they never cross the
    network in a real deployment (the average mirrors Alg. 1's replica
    consensus, not a transmitted payload).

    ``streaming=True`` emits the *per-leaf* round (``engine.StreamingStar``
    semantics): one independent ``reduce_leaf`` per parameter leaf, in
    reverse-layer order — the order leaves finish their last local step
    under backprop. Numerics are bit-exact with the blocking round (each
    leaf folds the same per-leaf rng), but the reduce is expressed as
    per-leaf data-independent ops, so when the step runs under jit XLA's
    scheduler is free to interleave leaf l's reduce with the remaining
    leaves' compute instead of waiting on one whole-tree collective. The
    consensus broadcast is emitted per leaf inside the same loop (the
    downlink mirror of the per-leaf uplink). Composes with
    ``hierarchical=True``: the two-level round then runs per leaf too
    (``Hierarchical(streaming=True)`` — intra-pod reduce feeding the
    inter-pod reduce leaf by leaf).

    ``hierarchical=True`` emits the *two-level* round
    (``engine.Hierarchical`` semantics, see ``docs/topologies.md``): an
    intra-pod reduce with ``reducer`` (dense by default — the hop rides
    cheap ICI) followed by an inter-pod reduce of the ``n_pods`` pod means
    with ``inter_reducer`` (int8-EF by default — the hop crosses the WAN).
    Clients are pods' contiguous slices of the leading client axis, the
    layout a ``(pod, data, model)`` mesh shards pod-major, so under pjit
    the intra hop's collectives stay on the ``data`` axis and the inter
    hop's on the ``pod`` axis — the driver's collectives structurally
    match what the ``Hierarchical`` cost model prices. The round *is*
    ``Hierarchical.reduce`` (one shared code path), so it is bit-exact
    with the simulator's hierarchical trace on the same rng; per-hop
    error-feedback residuals ride in ``state["comm"]``. Degenerate cases
    keep the flat contract exactly: ``n_pods=1`` (no inter-pod link
    exists) and dense∘dense (the two-level mean collapses to the flat
    mean) both produce the flat round bit-exactly.

    ``mesh`` (with the ``client_axis`` the replicas are sharded over)
    keeps the round's output params and moments split over the client
    axis, as its input was. Where that axis holds one replica per device
    and no other axis splits a leaf, the dense blocking round averages
    its narrow leaves through ``_gathered_mean``; each round lowered counts
    once under ``sync.lowered{path=scatter_gather|all_reduce}``.
    """
    reducer = get_reducer(reducer)
    dense = isinstance(reducer, DenseMean)

    if hierarchical:
        if n_pods < 1:
            raise ValueError(f"n_pods must be >= 1, got {n_pods}")
        if n_pods > 1:
            return _build_two_level_sync_step(reducer, n_pods, inter_reducer,
                                              base_seed, streaming, mesh,
                                              client_axis)
        # n_pods == 1: a single pod has no inter-pod hop to cross — the
        # round degenerates to the flat round with the intra reducer
        # (streaming or blocking; bit-exact with the flat path by
        # construction; the inter reducer is unused because no WAN link
        # exists)

    def sync_step(state):
        # named scopes "reduce" and "broadcast" name the round's two halves
        # in a profile's op metadata
        n = jax.tree.leaves(state["params"])[0].shape[0]
        gather = (dense and not streaming
                  and _one_replica_per_device(mesh, client_axis, n))
        rng = jax.random.fold_in(jax.random.key(base_seed), state["step"])
        comm = None
        with jax.named_scope("reduce"):
            opt = tree_mean_leading(state["opt"])
            if dense and not streaming:
                params = tree_mean_leading(state["params"])
            elif dense:
                # streaming dense round: per-leaf mean + per-leaf
                # rebroadcast inside the same reversed loop (state tree
                # untouched, like the blocking dense round; rng unused) —
                # leaf l's reduce and downlink broadcast form one
                # data-independent unit under jit
                params, _ = reduce_streaming(reducer, state["params"], None,
                                             rng, broadcast_n=n)
            else:
                comm = state.get("comm")
                if comm is None:
                    comm = reducer.init_state(state["params"])
                if streaming:
                    params, comm = reduce_streaming(
                        reducer, state["params"], comm, rng, broadcast_n=n)
                else:
                    params, comm = reducer.reduce(state["params"], comm, rng)
        with jax.named_scope("broadcast"):
            out = dict(state, opt=tree_broadcast_leading(opt, n),
                       params=(params if streaming
                               else tree_broadcast_leading(params, n)))
            if comm is not None:
                out["comm"] = comm
            out.update(_client_sharded({"params": out["params"],
                                        "opt": out["opt"]}, mesh,
                                       client_axis))
        if gather:
            out.update({k: _gather_narrow(state[k], out[k], mesh, client_axis)
                        for k in ("params", "opt")})
        out["step"] = _sync_lowered(
            state["step"], path="scatter_gather" if gather else "all_reduce")
        return out

    # tag the step with its reducer (and round structure) so
    # StagewiseDriver's comm accounting can't drift from what the round
    # actually transmits
    sync_step.reducer = reducer
    sync_step.streaming = streaming
    sync_step.hierarchical = False
    return sync_step


def _build_two_level_sync_step(intra, n_pods: int, inter_reducer,
                               base_seed: int, streaming: bool = False,
                               mesh=None, client_axis="data"):
    """The hierarchical (n_pods > 1) round behind ``build_sync_step``.

    One ``engine.Hierarchical.reduce`` per sync — the same code path the
    vmapped simulator executes for ``topology="hier"`` — with the per-hop
    reducer state riding in ``state["comm"]`` (created on first sync, like
    the flat compressed round). The dense∘dense configuration keeps the
    state tree untouched: ``Hierarchical`` collapses it to the flat mean
    and its reducer state is inert, so the round matches the flat dense
    round exactly, key set included.

    ``streaming=True`` executes the same round per leaf
    (``Hierarchical(streaming=True)``): leaf l's intra-pod reduce feeds
    its inter-pod reduce immediately, in reverse-layer order, so under
    jit the WAN collective of late leaves is free to overlap the
    intra-pod reduction of the early ones. Bit-exact with the blocking
    two-level round (same per-leaf rng folds on both hops).
    """
    from repro.engine.topology import Hierarchical

    inter = get_reducer(inter_reducer)
    topo = Hierarchical(n_pods=n_pods, intra=intra, inter=inter,
                        streaming=streaming)

    def sync_step(state):
        n = jax.tree.leaves(state["params"])[0].shape[0]
        if n % n_pods:
            # concrete at trace time — same contract as Hierarchical
            raise ValueError(
                f"{n} client replicas not divisible into {n_pods} pods")
        rng = jax.random.fold_in(jax.random.key(base_seed), state["step"])
        comm = None
        with jax.named_scope("reduce"):
            opt = tree_mean_leading(state["opt"])
            if topo.all_dense:
                consensus, _ = topo.reduce(state["params"], None, rng)
            else:
                comm = state.get("comm")
                if comm is None:
                    comm = topo.init_state(state["params"])
                consensus, comm = topo.reduce(state["params"], comm, rng)
        with jax.named_scope("broadcast"):
            out = dict(state, params=tree_broadcast_leading(consensus, n),
                       opt=tree_broadcast_leading(opt, n))
            if comm is not None:
                out["comm"] = comm
            out.update(_client_sharded({"params": out["params"],
                                        "opt": out["opt"]}, mesh,
                                       client_axis))
        return out

    # tags: the driver prices the topology the round actually executes
    sync_step.reducer = intra
    sync_step.streaming = streaming
    sync_step.hierarchical = True
    sync_step.n_pods = n_pods
    sync_step.inter_reducer = inter
    return sync_step


def sync_step_tags(sync_step) -> dict:
    """The comm tags ``build_sync_step`` stamped on a round, read through
    any stack of wrappers that chain ``__wrapped__`` (``jax.jit``,
    ``functools.wraps`` decorators like ``obs.ProfileSession.wrap``).

    Returns ``{"reducer", "streaming", "hierarchical"}`` plus
    ``{"n_pods", "inter_reducer"}`` for two-level rounds; absent tags come
    back ``None``/``False``. ``StagewiseDriver`` reads its comm accounting
    *and* its trace-span attributes from here, so the priced ledger and
    the exported timeline can't drift from the round the step executes.
    """
    def tag(name, default=None):
        fn, v = sync_step, None
        for _ in range(8):   # walk the full wrapper chain (cycle-safe)
            if fn is None:
                break
            v = getattr(fn, name, None)
            if v is not None:
                break
            fn = getattr(fn, "__wrapped__", None)
        return default if v is None else v

    tags = {"reducer": tag("reducer"),
            "streaming": bool(tag("streaming", False)),
            "hierarchical": bool(tag("hierarchical", False))}
    if tags["hierarchical"]:
        tags["n_pods"] = tag("n_pods")
        tags["inter_reducer"] = tag("inter_reducer")
    return tags


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
        for a compressed round, default dense; ``streaming=True`` for the
        per-leaf reduce XLA can overlap with compute)

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
