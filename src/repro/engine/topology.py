"""Topology — *where* a communication round's bytes travel.

A topology composes reducers over hops and prices each hop with its own
α–β ``NetworkModel``:

  Star          the paper's setting: every client uplinks to one server
                over a single link (one hop, one reducer).
  Hierarchical  pod/WAN deployment: a dense intra-pod reduce over fast ICI
                followed by a (typically compressed) inter-pod reduce over
                the slow WAN. Clients split into ``n_pods`` equal pods on
                the leading replica axis; pod reductions run in parallel,
                so the intra hop's modeled time uses per-pod bytes while
                its byte count is the total traffic.

Topologies expose the same ``init_state`` / ``reduce`` protocol as a
``comm.Reducer`` (state is a pytree, reduce is jit/scan-safe), so the round
function is agnostic to whether it averages over one hop or two — and
``hop_costs`` replaces the single-link cost model with a per-hop
(latency, bandwidth) list.
"""
from __future__ import annotations

from dataclasses import dataclass, field, replace
from typing import List, Optional

import jax
import jax.numpy as jnp

from repro.comm.cost import (NetworkModel, dense_bytes, link_model,
                             round_time)
from repro.comm.reducer import (DenseMean, Reducer, get_reducer,
                                reduce_streaming, supports_leaf_bytes)


@dataclass(frozen=True)
class HopCost:
    """Modeled cost of one hop of one communication round."""

    hop: str            # "uplink" | "intra_pod" | "inter_pod" | "downlink"
    reducer: str
    network: NetworkModel
    bytes: int          # total traffic crossing the hop per round
    time_s: float       # α + serial_bytes / bandwidth (parallel links once)


@dataclass(frozen=True)
class LeafCost:
    """Modeled cost of ONE leaf's share of one hop of one round.

    The per-leaf comm ledger: ``bytes`` is the total traffic that leaf's
    messages put on the hop per round (all clients), ``time_s`` its share of
    the hop's serial α–β time (the hop latency α is attributed to the
    hop's first leaf once, serialization is bytes/bandwidth). Summing a
    hop's LeafCosts reproduces the tree-level ``HopCost`` — bytes
    bit-exactly (integer per-leaf formulas), seconds to float-sum precision.
    """

    leaf: int           # index into jax.tree.leaves(template)
    path: str           # jax.tree_util.keystr of the leaf
    hop: str            # same hop names as HopCost
    bytes: int          # total per-round traffic of this leaf on this hop
    time_s: float       # this leaf's share of the hop's serial α–β time


def _leaf_paths(template) -> List[str]:
    """Human-readable key paths for every leaf of a template pytree."""
    paths, _ = jax.tree_util.tree_flatten_with_path(template)
    return [jax.tree_util.keystr(p) for p, _ in paths]


def _hop_leaf_costs(hop: str, leaf_bytes, paths, net: NetworkModel, *,
                    mult: int, tmult: Optional[int] = None) -> List[LeafCost]:
    """One hop's LeafCost rows from per-leaf message bytes.

    ``bytes`` is ``mult`` messages' worth of traffic per leaf; time is
    ``tmult`` (default ``mult``) messages' serialization on ``net`` — they
    differ only for parallel intra-pod links, where the hop's byte count is
    the total traffic but its time sees one pod's. The hop latency α is
    attributed to the hop's first leaf once.
    """
    tmult = mult if tmult is None else tmult
    out = []
    for i, (b, p) in enumerate(zip(leaf_bytes, paths)):
        t = tmult * b / net.bandwidth_Bps
        if i == 0:
            t += net.latency_s
        out.append(LeafCost(leaf=i, path=p, hop=hop,
                            bytes=mult * b, time_s=t))
    return out


class Topology:
    """Base protocol — reducer-compatible reduce + per-hop costing."""

    name = "base"

    def init_state(self, stacked):
        """Reducer state (EF residuals per hop) for the stacked (N, ...)
        replica tree; call at run start when replicas are identical."""
        raise NotImplementedError

    def reduce(self, stacked, state, rng):
        """Route one round: (stacked replicas, state, rng) -> (consensus
        tree without the client axis, new state). jit/scan-safe."""
        raise NotImplementedError

    @property
    def stateful(self) -> bool:
        """Whether ``reduce`` reads and returns reducer state (error-feedback
        residuals); a stateless round ignores the state it is given."""
        raise NotImplementedError

    def on_link(self, network: NetworkModel) -> "Topology":
        """This round priced over ``network``: the hop a config's α–β link
        describes (the uplink of a star, the inter-pod hop of pods)."""
        raise NotImplementedError

    def hop_costs(self, template, n_clients: int) -> List[HopCost]:
        """Price one round hop by hop: total payload bytes crossing each
        hop and its serial α–β time in modeled seconds (``template`` is a
        single-replica pytree of arrays or ShapeDtypeStructs)."""
        raise NotImplementedError

    def leaf_costs(self, template, n_clients: int) -> List[LeafCost]:
        """Per-(leaf, hop) breakdown of one round's modeled cost.

        Empty by default (a topology without per-leaf accounting); Star,
        StreamingStar and Hierarchical implement it so the engine ledger can
        reconcile streaming per-leaf uploads against tree-level totals.
        """
        return []

    def round_bytes(self, template, n_clients: int) -> int:
        """Total modeled payload bytes one round moves across all hops."""
        return sum(h.bytes for h in self.hop_costs(template, n_clients))

    def round_time(self, template, n_clients: int) -> float:
        """Total serial α–β time of one round across all hops, in modeled
        seconds (parallel intra-pod links are priced once)."""
        return sum(h.time_s for h in self.hop_costs(template, n_clients))

    def summary(self, template, n_clients: int, n_rounds: int) -> dict:
        """Full per-hop comm report for a finished run."""
        hops = self.hop_costs(template, n_clients)
        per_round = sum(h.bytes for h in hops)
        t_round = sum(h.time_s for h in hops)
        return {
            "topology": self.name,
            "rounds": int(n_rounds),
            "bytes_per_round": int(per_round),
            "total_bytes": int(per_round) * int(n_rounds),
            "round_time_s": t_round,
            "total_time_s": t_round * int(n_rounds),
            "hops": [{
                "hop": h.hop, "reducer": h.reducer,
                "latency_s": h.network.latency_s,
                "bandwidth_gbps": h.network.bandwidth_gbps,
                "bytes_per_round": int(h.bytes),
                "time_per_round_s": h.time_s,
                "total_time_s": h.time_s * int(n_rounds),
            } for h in hops],
        }


@dataclass(frozen=True)
class Star(Topology):
    """Flat parameter-server topology — the paper's setting, one hop.

    With ``reducer=DenseMean()`` this is bit-exact with calling the reducer
    directly (the pre-engine behavior).
    """

    reducer: Reducer = field(default_factory=DenseMean)
    network: NetworkModel = field(default_factory=NetworkModel)

    name = "star"
    streaming = False

    def init_state(self, stacked):
        return self.reducer.init_state(stacked)

    def reduce(self, stacked, state, rng):
        return self.reducer.reduce(stacked, state, rng)

    @property
    def stateful(self) -> bool:
        return not isinstance(self.reducer, DenseMean)

    def on_link(self, network):
        return replace(self, network=network)

    def hop_costs(self, template, n_clients: int) -> List[HopCost]:
        up = n_clients * self.reducer.message_bytes(template)
        hops = [HopCost(hop="uplink", reducer=self.reducer.name,
                        network=self.network, bytes=up,
                        time_s=round_time(self.network, up))]
        if self.network.count_downlink:
            # the dense server broadcast is its own hop (cost_model.md:
            # reducer-independent, billed only on count_downlink links) —
            # sum of hop bytes still equals ``cost.round_bytes``
            down = n_clients * dense_bytes(template)
            hops.append(HopCost(hop="downlink", reducer="dense",
                                network=self.network, bytes=down,
                                time_s=round_time(self.network, down)))
        return hops

    def leaf_costs(self, template, n_clients: int) -> List[LeafCost]:
        if not supports_leaf_bytes(self.reducer):
            # custom reducers predating the per-leaf protocol (only
            # message_bytes overridden) still run — without a leaf ledger
            return []
        leaf_bytes = self.reducer.leaf_message_bytes(template)
        paths = _leaf_paths(template)
        out = _hop_leaf_costs("uplink", leaf_bytes, paths, self.network,
                              mult=n_clients)
        if self.network.count_downlink:
            # mirror hop_costs: the dense broadcast gets its own downlink
            # rows, so the ledger reconciles hop by hop
            down = DenseMean().leaf_message_bytes(template)
            out += _hop_leaf_costs("downlink", down, paths, self.network,
                                   mult=n_clients)
        return out


@dataclass(frozen=True)
class StreamingStar(Star):
    """Star whose reduce runs *per leaf* — the streaming execution topology.

    Numerics are bit-exact with ``Star`` (each leaf is reduced with the
    same per-leaf rng the tree-level reducer folds), but the reduction is
    expressed as one independent ``reduce_leaf`` call per leaf, in
    reverse-layer order — the order leaves finish their last local step
    under backprop. That is the structure a jit'd sync step needs for XLA
    to interleave leaf l's reduce with the remaining leaves' compute, and
    it is what ``local_sgd.build_sync_step(streaming=True)`` runs; the
    cost model (``hop_costs`` / ``leaf_costs``) is inherited unchanged, so
    streaming and blocking ledgers reconcile by construction. The modeled
    *overlap* win is priced by ``runtime.StreamingSchedule``, not here —
    the ledger stays the serial α–β view.
    """

    name = "streaming-star"
    streaming = True

    def reduce(self, stacked, state, rng):
        """The per-leaf round: ``comm.reduce_streaming`` over the uplink
        reducer (one shared copy of the reverse-order + per-leaf-rng
        structure, so execution paths cannot drift)."""
        return reduce_streaming(self.reducer, stacked, state, rng)


@dataclass(frozen=True)
class Hierarchical(Topology):
    """Two-level pod topology: intra-pod reduce (fast link), then inter-pod
    reduce over the pod means (slow link).

    The client axis must be divisible by ``n_pods``. Pod p's replicas are
    the contiguous slice [p·m, (p+1)·m) of the leading client axis — the
    layout a ``(pod, data, model)`` mesh shards pod-major, so when the
    stacked replica tree is sharded ``P(("pod", "data"), ...)`` this reduce
    *is* the driver's two-level round: the intra hop (a reshaped mean over
    the per-pod slice) lowers to collectives on the ``data`` mesh axis
    only, and the inter hop (``inter.reduce`` over the ``n_pods`` stacked
    pod means) to collectives on the ``pod`` axis only.
    ``local_sgd.build_sync_step(hierarchical=True)`` runs exactly this
    method, so the driver's collectives and the simulator's hierarchical
    trace are the same code path (bit-exact on the same rng).

    Both levels keep their own reducer state (error-feedback residuals
    live per level), so e.g. a dense ICI average composes with an int8-EF
    WAN round. Per-round rng discipline: pod p's intra reduce folds
    ``fold_in(rng, p)``; the inter reduce folds ``fold_in(rng, n_pods)``.

    Dense∘dense collapse: with ``DenseMean`` on *both* hops the two-level
    round is algebraically the flat mean over all clients (equal-size
    pods), so it is computed as exactly that — one fused mean. This keeps
    the dense-WAN two-level round bit-exact with the flat ``Star`` path
    (the driver's safety-rail contract) instead of merely close to it; the
    per-hop cost model still prices both hops.

    ``streaming=True`` is the streaming∘hierarchical composition: the
    two-level round runs *per leaf* in reverse-layer order — leaf l's
    intra-pod reduce feeds its inter-pod reduce immediately, so the WAN
    hop of early-finishing leaves overlaps the intra-pod reduction of the
    remaining leaves. Numerics are bit-exact with the blocking
    ``Hierarchical`` round (every hop folds the same per-leaf rng its
    tree-level reduce folds), the cost model is inherited unchanged (the
    ledger stays the serial α–β view), and the modeled overlap win is
    priced by ``runtime.StreamingSchedule``. At ``n_pods=1`` the spec
    resolver (``get_topology``) degenerates the round to ``StreamingStar``
    (flat ``Star`` when blocking) — the single-pod round *is* the flat
    round, matching the driver contract.
    """

    n_pods: int = 2
    intra: Reducer = field(default_factory=DenseMean)
    inter: Reducer = field(default_factory=DenseMean)
    intra_net: NetworkModel = field(default_factory=lambda: link_model("ici"))
    inter_net: NetworkModel = field(default_factory=lambda: link_model("wan"))
    streaming: bool = False

    @property
    def name(self) -> str:
        return "streaming-hier" if self.streaming else "hierarchical"

    @property
    def stateful(self) -> bool:
        """False when both hops are DenseMean — the collapsible case."""
        return not (type(self.intra) is DenseMean
                    and type(self.inter) is DenseMean)

    def on_link(self, network):
        return replace(self, inter_net=network)

    def _pods(self, stacked):
        P = self.n_pods
        return [jax.tree.map(lambda x: x[p * (x.shape[0] // P):
                                         (p + 1) * (x.shape[0] // P)], stacked)
                for p in range(P)]

    def _pod_means(self, stacked):
        """Dense intra hop as one reshaped mean: (N, ...) -> (n_pods, ...).

        The reshape splits the client axis pod-major — a layout no-op on a
        ``P(("pod", "data"))``-sharded axis — so the mean reduces over the
        ``data`` axis only and never crosses pods.
        """
        P = self.n_pods
        return jax.tree.map(
            lambda x: jnp.mean(
                x.reshape((P, x.shape[0] // P) + x.shape[1:]), axis=1),
            stacked)

    def _check_pods(self, stacked):
        n = jax.tree.leaves(stacked)[0].shape[0]
        if n % self.n_pods:
            raise ValueError(
                f"{n} clients not divisible into {self.n_pods} pods")

    def init_state(self, stacked):
        self._check_pods(stacked)
        pods = self._pods(stacked)
        return {"intra": tuple(self.intra.init_state(p) for p in pods),
                "inter": self.inter.init_state(self._pod_means(stacked))}

    def reduce(self, stacked, state, rng):
        self._check_pods(stacked)
        if self.streaming:
            return self._reduce_streaming(stacked, state, rng)
        if not self.stateful:
            # see class docstring: dense∘dense ≡ the flat mean, computed
            # as such so the two-level round is bit-exact with Star
            return jax.tree.map(lambda x: jnp.mean(x, axis=0),
                                stacked), state
        if type(self.intra) is DenseMean:
            # stateless, rng-free intra hop: one fused per-pod mean whose
            # collectives stay on the intra-pod (data) axis under pjit
            stacked_means = self._pod_means(stacked)
            intra_states = state["intra"]
        else:
            means, intra_states = [], []
            for p, pod in enumerate(self._pods(stacked)):
                m, st = self.intra.reduce(pod, state["intra"][p],
                                          jax.random.fold_in(rng, p))
                means.append(m)
                intra_states.append(st)
            stacked_means = jax.tree.map(lambda *xs: jnp.stack(xs), *means)
            intra_states = tuple(intra_states)
        consensus, inter_state = self.inter.reduce(
            stacked_means, state["inter"],
            jax.random.fold_in(rng, self.n_pods))
        return consensus, {"intra": intra_states,
                           "inter": inter_state}

    def _reduce_streaming(self, stacked, state, rng):
        """The per-leaf two-level round (``streaming=True`` execution).

        Leaves run in reverse-layer order; for each leaf the intra-pod
        reduce feeds the inter-pod reduce immediately. Bit-exactness with
        the blocking ``reduce``: pod p's intra hop folds
        ``fold_in(fold_in(rng, p), leaf)`` — exactly what
        ``intra.reduce``'s internal per-leaf loop folds under
        ``fold_in(rng, p)`` — and the inter hop folds
        ``fold_in(fold_in(rng, n_pods), leaf)`` likewise. The dense-intra
        fused-pod-means and dense∘dense flat-mean specializations of the
        blocking path are preserved per leaf (state passes through
        untouched where the blocking round leaves it untouched).
        """
        leaves, treedef = jax.tree.flatten(stacked)
        P = self.n_pods
        if not self.stateful:
            out = [None] * len(leaves)
            for i in reversed(range(len(leaves))):
                out[i] = jnp.mean(leaves[i], axis=0)
            return treedef.unflatten(out), state
        dense_intra = type(self.intra) is DenseMean
        if not dense_intra:
            intra_states = [self.intra.split_state(state["intra"][p], treedef)
                            for p in range(P)]
        inter_states = self.inter.split_state(state["inter"], treedef)
        out = [None] * len(leaves)
        for i in reversed(range(len(leaves))):
            x = leaves[i]
            m = x.shape[0] // P
            if dense_intra:
                pod_means = jnp.mean(
                    x.reshape((P, m) + x.shape[1:]), axis=1)
            else:
                pms = []
                for p in range(P):
                    pm, intra_states[p][i] = self.intra.reduce_leaf(
                        x[p * m:(p + 1) * m], intra_states[p][i],
                        jax.random.fold_in(jax.random.fold_in(rng, p), i))
                    pms.append(pm)
                pod_means = jnp.stack(pms)
            out[i], inter_states[i] = self.inter.reduce_leaf(
                pod_means, inter_states[i],
                jax.random.fold_in(jax.random.fold_in(rng, P), i))
        new_intra = (state["intra"] if dense_intra else
                     tuple(self.intra.join_state(intra_states[p], treedef)
                           for p in range(P)))
        return treedef.unflatten(out), {
            "intra": new_intra,
            "inter": self.inter.join_state(inter_states, treedef)}

    def hop_costs(self, template, n_clients: int) -> List[HopCost]:
        if n_clients % self.n_pods:
            # same shape contract as init_state/reduce — pricing must not
            # succeed for a configuration execution would reject
            raise ValueError(
                f"{n_clients} clients not divisible into {self.n_pods} pods")
        m = n_clients // self.n_pods
        intra_msg = self.intra.message_bytes(template)
        inter_msg = self.inter.message_bytes(template)
        intra_total = n_clients * intra_msg
        inter_total = self.n_pods * inter_msg
        hops = [
            # pods reduce in parallel: time sees one pod's traffic
            HopCost(hop="intra_pod", reducer=self.intra.name,
                    network=self.intra_net, bytes=intra_total,
                    time_s=self.intra_net.latency_s
                    + m * intra_msg / self.intra_net.bandwidth_Bps),
            HopCost(hop="inter_pod", reducer=self.inter.name,
                    network=self.inter_net, bytes=inter_total,
                    time_s=self.inter_net.latency_s
                    + inter_total / self.inter_net.bandwidth_Bps),
        ]
        if self.inter_net.count_downlink:
            # the global consensus broadcast rides the slow (WAN) link back
            # to every client — dense and reducer-independent, like Star's
            down = n_clients * dense_bytes(template)
            hops.append(HopCost(hop="downlink", reducer="dense",
                                network=self.inter_net, bytes=down,
                                time_s=round_time(self.inter_net, down)))
        return hops

    def leaf_costs(self, template, n_clients: int) -> List[LeafCost]:
        """Per-leaf ledger across both hops, mirroring ``hop_costs``:
        intra-pod time sees one pod's per-leaf traffic (pods run in
        parallel), inter-pod time the pod-mean messages; each hop's α is
        attributed to its first leaf once."""
        if n_clients % self.n_pods:
            raise ValueError(
                f"{n_clients} clients not divisible into {self.n_pods} pods")
        if not (supports_leaf_bytes(self.intra)
                and supports_leaf_bytes(self.inter)):
            return []  # pre-per-leaf-protocol custom reducer: no ledger
        m = n_clients // self.n_pods
        paths = _leaf_paths(template)
        out = _hop_leaf_costs("intra_pod",
                              self.intra.leaf_message_bytes(template),
                              paths, self.intra_net,
                              mult=n_clients, tmult=m)
        out += _hop_leaf_costs("inter_pod",
                               self.inter.leaf_message_bytes(template),
                               paths, self.inter_net, mult=self.n_pods)
        if self.inter_net.count_downlink:
            out += _hop_leaf_costs("downlink",
                                   DenseMean().leaf_message_bytes(template),
                                   paths, self.inter_net, mult=n_clients)
        return out


def get_topology(spec, *, reducer=None, network: Optional[NetworkModel] = None,
                 n_pods: int = 2, inter_reducer=None,
                 quant_bits: int = 8, topk_frac: float = 0.1) -> Topology:
    """Resolve a topology from a config string (or pass one through).

    "star" (default) wraps ``reducer`` in the single-hop paper topology;
    "streaming"/"streaming-star" is the same hop but reduced per leaf
    (communication/compute overlap — see ``StreamingStar``);
    "hier"/"hierarchical" composes ``reducer`` intra-pod (dense by default)
    with ``inter_reducer`` (int8 by default) inter-pod over calibrated
    ICI/WAN links; "streaming-hier"/"hier-streaming" is the same two-level
    round reduced per leaf (``Hierarchical(streaming=True)``).

    Single-pod degeneracy: a hierarchical spec with ``n_pods=1`` has no
    inter-pod link, so it resolves to the flat round over ``reducer`` —
    ``Star`` (blocking) or ``StreamingStar`` (streaming): one pod *is* the
    flat star. The pjit round (``local_sgd.build_sync_step``) resolves
    its topology here too, so both backends run what this returns.
    """
    if isinstance(spec, Topology):
        return spec
    red = get_reducer(reducer, quant_bits=quant_bits, topk_frac=topk_frac)
    if spec in (None, "star", "flat"):
        return Star(reducer=red, network=network or NetworkModel())
    if spec in ("streaming", "streaming-star", "stream"):
        return StreamingStar(reducer=red, network=network or NetworkModel())
    hier_specs = ("hier", "hierarchical", "pods")
    stream_hier_specs = ("streaming-hier", "hier-streaming",
                         "streaming-hierarchical")
    if spec in hier_specs + stream_hier_specs:
        streaming = spec in stream_hier_specs
        if n_pods < 1:
            raise ValueError(f"n_pods must be >= 1, got {n_pods}")
        if n_pods == 1:
            cls = StreamingStar if streaming else Star
            return cls(reducer=red, network=network or NetworkModel())
        inter = get_reducer(inter_reducer if inter_reducer is not None
                            else "int8", quant_bits=quant_bits,
                            topk_frac=topk_frac)
        return Hierarchical(n_pods=n_pods, intra=red, inter=inter,
                            intra_net=link_model("ici"),
                            inter_net=network or link_model("wan"),
                            streaming=streaming)
    raise ValueError(f"unknown topology spec: {spec!r}")
