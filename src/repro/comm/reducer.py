"""Reducer protocol — pluggable compression for the communication round.

A *reducer* owns Algorithm 1 line 5 (the parameter average). Compressed
reducers follow the standard error-feedback template over *round deltas*:
every client starts the round at the shared consensus ``ref``; after its k
local steps it uploads

    m_i = C((x_i - ref) + e_i)          (compress delta + carried residual)
    e_i' = (x_i - ref) + e_i - m_i      (what the compressor dropped)

and the server forms the next consensus ``ref' = ref + mean_i m_i``. Deltas
have far smaller dynamic range than raw parameters, so the same bit budget
buys much less distortion, and the residual state e_i makes the scheme
convergent (EF-SGD-style) even for biased compressors like top-k. This
composes the paper's axis (fewer rounds, stagewise k_s) with the orthogonal
axis (cheaper rounds, fewer bytes per round).

All reducers are pure pytree->pytree functions of (stacked replicas,
state, rng), safe inside jit / lax.scan — state keeps a stable tree
structure across calls.

Implementations
  DenseMean     — identity compression; bit-exact with tree_mean_leading.
  QuantizedMean — int8 (or narrower) symmetric stochastic-rounding delta
                  quantization per (client, leaf); Pallas-fused kernels in
                  repro.kernels.quantize (impl="interpret"/"pallas") or the
                  jnp oracle (impl="xla", default — fastest on CPU).
  TopKMean      — magnitude top-k delta sparsification per (client, leaf);
                  messages are (value, index) pairs.

``message_bytes(template)`` reports the compressed uplink payload one client
sends per round — the quantity comm.cost prices. ``template`` is a
single-replica pytree (arrays or ShapeDtypeStructs).
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp

from repro.kernels.quantize import ops as Q
from repro.obs import metrics as obs_metrics
from repro.utils.tree import tree_mean_leading

_EPS = 1e-12


def _leaf_elems(leaf) -> int:
    size = 1
    for d in leaf.shape:
        size *= d
    return size


class Reducer:
    """Base protocol. Subclasses override the tree-level ``reduce()`` and the
    per-leaf byte accounting (``leaf_message_bytes``); the per-leaf reduce
    protocol (``split_state`` / ``reduce_leaf`` / ``join_state``) is what the
    streaming execution paths (``engine.StreamingStar``,
    ``local_sgd.build_sync_step(streaming=True)``) drive — leaf by leaf, same
    numerics as the tree-level call."""

    name = "base"

    def init_state(self, stacked):
        """Residual/reference state for the stacked (N, ...) replica tree.

        Call at run start, when all replicas are identical (post-broadcast).
        """
        return None

    def reduce(self, stacked, state, rng):
        """(stacked replicas, state, rng) -> (consensus tree, new state).

        The consensus tree has the leading client axis removed; callers
        rebroadcast it (tree_broadcast_leading) to continue local training.
        """
        raise NotImplementedError

    # -- per-leaf protocol (streaming reduce) -------------------------------

    def split_state(self, state, treedef):
        """Split the reducer state into one per-leaf slice.

        ``treedef`` is the stacked replica tree's structure; the returned
        list is index-aligned with ``jax.tree.flatten(stacked)[0]``. The
        base (stateless) implementation yields ``None`` per leaf.
        """
        return [None] * treedef.num_leaves

    def join_state(self, leaf_states, treedef) -> "object":
        """Inverse of ``split_state``: rebuild the tree-level state."""
        return None

    def reduce_leaf(self, x, leaf_state, rng):
        """Reduce ONE stacked (N, ...) leaf -> (consensus leaf, new state).

        Leaves are independent, so calling this per leaf — in any order,
        with the same per-leaf rng the tree-level ``reduce`` would fold —
        is bit-exact with one tree-level call. This is the unit the
        streaming paths interleave with per-leaf compute.
        """
        raise NotImplementedError

    # -- byte accounting ----------------------------------------------------

    def leaf_message_bytes(self, template) -> list:
        """Per-leaf compressed uplink payload, in bytes, one client sends
        per round — index-aligned with ``jax.tree.leaves(template)``. The
        per-leaf comm ledger (``engine.Topology.leaf_costs``) and the
        streaming upload schedule (``runtime.StreamingSchedule``) consume
        this; ``message_bytes`` is its sum, so the two views reconcile
        bit-exactly by construction.
        """
        raise NotImplementedError

    def message_bytes(self, template) -> int:
        """Total compressed uplink bytes one client sends per round."""
        return sum(self.leaf_message_bytes(template))

    def __repr__(self):
        return f"{type(self).__name__}()"


@dataclass(frozen=True, repr=False)
class DenseMean(Reducer):
    """Uncompressed average — the pre-comm-subsystem behavior, bit-exact."""

    name = "dense"

    def reduce(self, stacked, state, rng):
        return tree_mean_leading(stacked), state

    # split_state / join_state: inherited stateless base implementations

    def reduce_leaf(self, x, leaf_state, rng):
        """Mean over the leading client axis of one leaf — the exact op
        ``tree_mean_leading`` applies per leaf, so per-leaf streaming is
        bit-exact with the tree-level average."""
        return jnp.mean(x, axis=0), leaf_state

    def leaf_message_bytes(self, template) -> list:
        """Raw leaf payloads: elements × itemsize bytes per leaf."""
        return [_leaf_elems(l) * jnp.dtype(l.dtype).itemsize
                for l in jax.tree.leaves(template)]


class _DeltaReducer(Reducer):
    """Shared error-feedback-over-deltas machinery for compressed reducers.

    Subclasses implement ``_compress(y, rng) -> (deq, mean)`` on a (N, M)
    f32 block of per-client deltas: ``deq`` is each client's decompressed
    message (N, M), ``mean`` its average (M,).
    """

    error_feedback: bool = True

    def init_state(self, stacked):
        return {
            # ref: the shared consensus every client started the round from
            "ref": jax.tree.map(lambda x: x[0].astype(jnp.float32), stacked),
            # res: per-client residual the compressor dropped so far
            "res": jax.tree.map(
                lambda x: jnp.zeros(x.shape, jnp.float32), stacked),
        }

    def split_state(self, state, treedef):
        refs = treedef.flatten_up_to(state["ref"])
        res = treedef.flatten_up_to(state["res"])
        return [{"ref": r, "res": e} for r, e in zip(refs, res)]

    def join_state(self, leaf_states, treedef):
        return {"ref": treedef.unflatten([s["ref"] for s in leaf_states]),
                "res": treedef.unflatten([s["res"] for s in leaf_states])}

    def reduce_leaf(self, x, leaf_state, rng):
        """One leaf's EF round: compress (delta + residual), average, carry
        the compression error forward. Same op order as the historical
        tree-level loop body, so per-leaf streaming is bit-exact."""
        r, e = leaf_state["ref"], leaf_state["res"]
        n = x.shape[0]
        y = (x.astype(jnp.float32).reshape(n, -1)
             - r.reshape(1, -1) + e.reshape(n, -1))
        deq, mean_delta = self._compress(y, rng)
        consensus = r.reshape(-1) + mean_delta
        drop = (y - deq) if self.error_feedback else jnp.zeros_like(y)
        return (consensus.reshape(r.shape).astype(x.dtype),
                {"ref": consensus.reshape(r.shape),
                 "res": drop.reshape(e.shape)})

    def reduce(self, stacked, state, rng):
        leaves, treedef = jax.tree.flatten(stacked)
        states = self.split_state(state, treedef)
        means, new_states = [], []
        for i, (x, st) in enumerate(zip(leaves, states)):
            consensus, ns = self.reduce_leaf(x, st, jax.random.fold_in(rng, i))
            means.append(consensus)
            new_states.append(ns)
        return treedef.unflatten(means), self.join_state(new_states, treedef)


@dataclass(frozen=True, repr=False)
class QuantizedMean(_DeltaReducer):
    """Symmetric stochastic-rounding delta quantization with error feedback.

    Per (client, leaf): scale = max|delta|, codes = SR(delta/scale * qmax)
    in ``bits``-bit signed range (stored int8). Stochastic rounding keeps the
    quantizer unbiased; the residual carries the lattice error forward.
    ``error_feedback=False`` gives the naive quantizer (for ablations — it
    stalls at the quantization noise floor where EF keeps converging).
    """

    bits: int = 8
    impl: str = "xla"  # "xla" | "interpret" | "pallas"
    error_feedback: bool = True
    # stochastic=False rounds to nearest (u = 0.5 constant): biased, only
    # safe together with error feedback — used by the EF ablation tests.
    stochastic: bool = True

    @property
    def name(self):
        return f"int{self.bits}" + ("" if self.error_feedback else "-noef")

    def _compress(self, y, rng):
        scales = jnp.maximum(jnp.max(jnp.abs(y), axis=1), _EPS)
        if self.stochastic:
            rbits = jax.random.bits(rng, y.shape, jnp.uint32)
        else:
            rbits = jnp.full(y.shape, 1 << 31, jnp.uint32)  # u = 0.5
        # the per-leaf kernel path: one self-contained encode/decode per
        # leaf, so streaming rounds can pipeline it against other leaves
        q = Q.encode_leaf(y, rbits, scales, bits=self.bits, impl=self.impl)
        deq, mean = Q.decode_mean_leaf(q, scales, bits=self.bits,
                                       impl=self.impl)
        return deq, mean

    def leaf_message_bytes(self, template) -> list:
        # bits-wide codes (packed) + one f32 scale per leaf
        return [-(-_leaf_elems(l) * self.bits // 8) + 4
                for l in jax.tree.leaves(template)]


@dataclass(frozen=True, repr=False)
class TopKMean(_DeltaReducer):
    """Magnitude top-k delta sparsification with error feedback.

    Per (client, leaf): keep the k = max(1, round(frac * size)) largest-
    magnitude delta entries; the rest accumulate into the residual.
    Messages are (f32 value, i32 index) pairs.
    """

    frac: float = 0.1
    error_feedback: bool = True

    @property
    def name(self):
        return f"top{self.frac:g}" + ("" if self.error_feedback else "-noef")

    def _k(self, size: int) -> int:
        return max(1, min(size, int(round(self.frac * size))))

    def _compress(self, y, rng):
        n = y.shape[0]
        k = self._k(y.shape[1])
        _, idx = jax.lax.top_k(jnp.abs(y), k)
        vals = jnp.take_along_axis(y, idx, axis=1)
        deq = jnp.zeros_like(y).at[jnp.arange(n)[:, None], idx].set(vals)
        return deq, jnp.sum(deq, axis=0) * (1.0 / n)

    def leaf_message_bytes(self, template) -> list:
        # (f32 value + i32 index) per kept entry
        return [8 * self._k(_leaf_elems(l))
                for l in jax.tree.leaves(template)]


@dataclass(frozen=True, repr=False)
class StalenessWeightedMean(_DeltaReducer):
    """Merge-on-arrival reducer for asynchronous rounds (repro.runtime).

    The barrier-free analogue of the delta reducers above: each client still
    uploads a (optionally int8-quantized, reusing the kernels behind
    ``QuantizedMean``) error-feedback-corrected round delta, but the server
    applies messages *as they arrive* instead of averaging a full cohort:

        server' = server + w(τ)/N · deq(C(Δ_i + e_i))
        w(τ)    = (1 + τ)^(-decay)

    where the staleness τ counts *server cycles beyond the natural pipeline
    lag*: in a steady barrier-free rotation every upload races the other
    N−1 clients' merges, so the runtime reports
    τ = max(0, merges_since_pull − (N−1)) / N — a client keeping pace
    merges at full weight (async ≈ sync in the homogeneous limit), while a
    straggler whose delta raced S extra full cycles is decayed by
    (1+S)^(−decay).

    The synchronous Reducer protocol (``reduce`` over a stacked cohort) is
    also implemented — all clients at τ=0 — so the topology/cost plumbing
    prices it like any other reducer; the per-message half (``encode`` /
    ``merge``) is what the event runtime drives.
    """

    decay: float = 0.5
    compress: str = "dense"   # "dense" | "int" (bits-wide quantization)
    bits: int = 8
    impl: str = "xla"
    error_feedback: bool = True

    @property
    def name(self):
        tag = "" if self.compress == "dense" else f"-int{self.bits}"
        return f"staleness{tag}"

    def weight(self, staleness: float) -> float:
        """Merge weight for a message that is ``staleness`` cycles late."""
        return (1.0 + max(0.0, float(staleness))) ** (-self.decay)

    def _compress(self, y, rng):
        if self.compress == "dense":
            return y, jnp.mean(y, axis=0)
        return QuantizedMean(bits=self.bits, impl=self.impl)._compress(y, rng)

    # -- per-message async protocol (driven by repro.runtime) ---------------

    def client_residual(self, template):
        """Fresh per-client error-feedback residual (f32 zeros tree)."""
        return jax.tree.map(lambda l: jnp.zeros(l.shape, jnp.float32),
                            template)

    def encode(self, delta, residual, rng):
        """One client's upload: compress (Δ + e).

        Returns (payload, residual') where ``payload`` is the decompressed
        f32 delta tree the server will apply and ``residual'`` carries what
        the compressor dropped (zeros when error feedback is off).
        """
        leaves, treedef = jax.tree.flatten(delta)
        res = treedef.flatten_up_to(residual)
        payloads, new_res = [], []
        for i, (d, e) in enumerate(zip(leaves, res)):
            y = (d.astype(jnp.float32) + e).reshape(1, -1)
            deq, _ = self._compress(y, jax.random.fold_in(rng, i))
            p = deq.reshape(d.shape)
            payloads.append(p)
            new_res.append((y.reshape(e.shape) - p) if self.error_feedback
                           else jnp.zeros_like(e))
        # encode runs eagerly once per upload (never inside jit), so this
        # is a safe per-message metric emission point
        m = obs_metrics.registry()
        m.counter("comm.messages", unit="messages",
                  help="async client uploads encoded").inc(
                      reducer=self.name)
        m.counter("comm.message_bytes", unit="B",
                  help="compressed payload bytes of async uploads").inc(
                      sum(self.leaf_message_bytes(delta)),
                      reducer=self.name)
        return treedef.unflatten(payloads), treedef.unflatten(new_res)

    def merge(self, server, payload, staleness: float, n_clients: int):
        """Apply one arrived message to the server model."""
        w = self.weight(staleness) / float(n_clients)
        obs_metrics.registry().histogram(
            "comm.merge_weight", unit="weight",
            help="staleness-decayed merge weights w(τ)/N applied").observe(
                w, reducer=self.name)
        return jax.tree.map(lambda s, p: s + w * p.astype(s.dtype),
                            server, payload)

    def leaf_message_bytes(self, template) -> list:
        if self.compress == "dense":
            return [_leaf_elems(l) * 4 for l in jax.tree.leaves(template)]
        return [-(-_leaf_elems(l) * self.bits // 8) + 4
                for l in jax.tree.leaves(template)]


def supports_leaf_bytes(reducer: Reducer) -> bool:
    """Explicit capability probe for the per-leaf byte protocol.

    True iff ``reducer`` *overrides* ``leaf_message_bytes`` — the callers
    that need per-leaf payloads (``engine.Topology.leaf_costs``, the event
    runtime's streaming schedules) branch on this probe instead of calling
    the method under ``except NotImplementedError``: a bug raised *inside*
    an implemented per-leaf method must propagate, never silently degrade
    to monolithic pricing.
    """
    return type(reducer).leaf_message_bytes is not Reducer.leaf_message_bytes


def reduce_streaming(reducer: Reducer, stacked, state, rng):
    """One streaming round: reduce the stacked replica tree leaf by leaf.

    The single copy of the per-leaf round structure every streaming
    execution path shares (``engine.StreamingStar.reduce``,
    ``local_sgd.build_sync_step(streaming=True)``): leaves are processed
    in *reverse-layer order* — the order they finish their last local
    step under backprop — and each leaf folds the same per-leaf rng the
    tree-level ``reducer.reduce`` folds (``fold_in(rng, leaf_index)``),
    so the result is bit-exact with the blocking round. Returns
    ``(consensus tree, new state)`` like ``Reducer.reduce``.
    """
    leaves, treedef = jax.tree.flatten(stacked)
    states = reducer.split_state(state, treedef)
    out = [None] * len(leaves)
    new = [None] * len(leaves)
    for i in reversed(range(len(leaves))):
        out[i], new[i] = reducer.reduce_leaf(
            leaves[i], states[i], jax.random.fold_in(rng, i))
    return treedef.unflatten(out), reducer.join_state(new, treedef)


def get_reducer(spec, *, quant_bits: int = 8, topk_frac: float = 0.1,
                impl: str = "xla", staleness_decay: float = 0.5) -> Reducer:
    """Resolve a reducer from a config string (or pass a Reducer through).

    Accepted specs: "dense" | "int8" / "quant" (quant_bits-wide) |
    "int<b>" (explicit width) | "topk" (topk_frac) |
    "staleness" / "staleness-int<b>" (async merge-on-arrival weights).
    """
    if isinstance(spec, Reducer):
        return spec
    if spec in (None, "dense", "mean"):
        return DenseMean()
    if spec in ("quant", "int8", "quantized"):
        b = 8 if spec == "int8" else quant_bits
        return QuantizedMean(bits=b, impl=impl)
    if spec == "staleness":
        return StalenessWeightedMean(decay=staleness_decay)
    if spec.startswith("staleness-int"):
        return StalenessWeightedMean(decay=staleness_decay, compress="int",
                                     bits=int(spec[len("staleness-int"):]),
                                     impl=impl)
    if spec.startswith("int"):
        return QuantizedMean(bits=int(spec[3:]), impl=impl)
    if spec == "topk":
        return TopKMean(frac=topk_frac)
    raise ValueError(f"unknown reducer spec: {spec!r}")
