"""Sharding rules: mesh axis names + activation constraints + param specs.

The production mesh axes (launch/mesh.py):
  pod   — inter-pod axis (multi-pod only)
  data  — client / batch axis (paper's N clients)
  model — tensor-parallel axis (heads / ffn / experts / vocab)

Model code calls ``shard(x, *spec)`` at layer boundaries; it is a no-op when
no mesh is active (CPU smoke tests) and filters axis names that the active
mesh does not carry, so the same model runs on 1 device, 256 or 512.
"""
from __future__ import annotations

from typing import Optional

import jax
from jax.sharding import PartitionSpec as P

DATA_AXIS = "data"
MODEL_AXIS = "model"
POD_AXIS = "pod"


def _filter(entry, names):
    if entry is None:
        return None
    if isinstance(entry, (tuple, list)):
        kept = tuple(a for a in entry if a in names)
        return kept if kept else None
    return entry if entry in names else None


def shard(x, *spec):
    """with_sharding_constraint that degrades gracefully.

    * no-op off-mesh (CPU smoke tests);
    * filters axis names absent from the active mesh;
    * SKIPS the whole constraint if any requested dim is not divisible by its
      mesh-axis size (e.g. 8 KV heads on a 16-way model axis) — forcing such a
      spec would trigger XLA's "involuntary full rematerialization"; leaving
      it unconstrained lets propagation pick a feasible layout instead.
    """
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty:
        return x
    sizes = dict(zip(mesh.axis_names, mesh.axis_sizes))
    names = set(sizes)
    fspec = tuple(_filter(e, names) for e in spec)
    for dim, entry in zip(x.shape, fspec):
        if entry is None:
            continue
        axes = entry if isinstance(entry, tuple) else (entry,)
        total = 1
        for a in axes:
            total *= sizes[a]
        if dim % total != 0:
            return x
    return jax.lax.with_sharding_constraint(x, P(*fspec))


# ---------------------------------------------------------------------------
# Parameter PartitionSpecs.
#
# Leaf-name driven: each rule gives the spec of the *trailing* dims of a leaf
# (the arch dims). Stack dims (layer-scan groups) and the client axis are
# prepended by the caller. ``model``-axis placement follows Megatron layout:
# column-parallel in-proj, row-parallel out-proj, experts sharded on E,
# embeddings on vocab.
# ---------------------------------------------------------------------------

_RULES = {
    # embeddings / head
    "embed": ("model", None),          # (vocab, d)
    "unembed": (None, "model"),        # (d, vocab)
    "proj_frontend": (None, None),     # (frontend_dim, d)
    # attention (gqa)
    "wq": (None, "model"),             # (d, H*hd)
    "wk": (None, "model"),
    "wv": (None, "model"),
    "wo": ("model", None),             # (H*hd, d)
    # attention (mla)
    "w_dq": (None, None),              # (d, q_lora)
    "w_uq": (None, "model"),           # (q_lora, H*(nope+rope))
    "w_dkv": (None, None),             # (d, kv_lora + rope)
    "w_uk": (None, "model"),           # (kv_lora, H*nope)
    "w_uv": (None, "model"),           # (kv_lora, H*v)
    # mlp
    "w_gate": (None, "model"),         # (d, ff)
    "w_up": (None, "model"),
    "w_down": ("model", None),         # (ff, d)
    # moe
    "w_router": (None, None),          # (d, E)
    "we_gate": ("model", None, None),  # (E, d, de)
    "we_up": ("model", None, None),
    "we_down": ("model", None, None),  # (E, de, d)
    # mamba2 / ssd
    "w_in": (None, "model"),           # (d, d_in_proj)
    "w_out_ssm": ("model", None),      # (d_inner, d)
    "conv_w": (None, "model"),         # (d_conv, conv_channels)
    "A_log": ("model",),               # (n_heads,)
    "D": ("model",),
    "dt_bias": ("model",),
    "ssm_norm": ("model",),            # (d_inner,) gated rmsnorm
    # rg-lru
    "w_x": (None, "model"),            # (d, lru)
    "w_gate_lru": (None, "model"),
    "conv_lru": (None, "model"),       # (d_conv, lru)
    "a_param": ("model",),             # (lru,)
    "w_in_gate": ("model", None),      # input-gate proj (lru, lru) row-parallel? keep simple
    "w_out_lru": ("model", None),      # (lru, d)
    "gate_w": ("model", None, None),   # per-channel gate (lru, small)
}

_REPLICATED_SUFFIXES = ("norm", "scale", "bias", "q_norm", "k_norm", "kv_norm")


def spec_for_leaf(name: str, ndim: int, extra_leading: int = 0):
    """PartitionSpec for a named leaf with `extra_leading` stack/client dims."""
    base: Optional[tuple]
    if name in _RULES:
        base = _RULES[name]
    elif any(name.endswith(s) for s in _REPLICATED_SUFFIXES):
        base = (None,) * (ndim - extra_leading)
    else:
        base = (None,) * (ndim - extra_leading)
    lead = (None,) * extra_leading
    spec = lead + tuple(base)
    assert len(spec) == ndim, f"{name}: spec {spec} vs ndim {ndim}"
    return P(*spec)


def param_specs(params, client_axis: Optional[str] = None,
                fsdp_axis: Optional[str] = None):
    """Pytree of PartitionSpecs matching ``params``.

    ``params`` leaves are named by their dict key; stacked-layer dims and the
    optional client axis are leading. client_axis ('data' or 'pod') is placed
    on dim 0 when given (training replicas); remaining leading dims (layer
    stacks) are unsharded. ``fsdp_axis`` (hierarchical mode: 'data') is added
    to the first unsharded weight dim — ZeRO-3-style intra-pod param sharding
    so pod-client replicas of 100B+ models fit HBM.
    """
    flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    specs = []
    for path, leaf in flat:
        name = None
        for entry in reversed(path):
            if isinstance(entry, jax.tree_util.DictKey):
                name = entry.key
                break
        name = name or "unnamed"
        base_ndim = _base_ndim(name, leaf.ndim, client_axis)
        extra = leaf.ndim - base_ndim
        spec = spec_for_leaf(name, leaf.ndim, extra_leading=extra)
        entries = list(tuple(spec))
        # Exclusions (§Perf A2/A2'): embed/unembed — FSDP on the table's
        # d_model dim turns every token lookup into a full re-gather; expert
        # weights — grouped dispatch re-gathers FSDP'd experts per group
        # (measured 6.8× collective regression), and they are already E-sharded
        # on `model`.
        if (fsdp_axis is not None and name in _RULES and base_ndim >= 2
                and name not in ("embed", "unembed",
                                 "we_gate", "we_up", "we_down")):
            for i in range(leaf.ndim - base_ndim, leaf.ndim):
                if entries[i] is None:
                    entries[i] = fsdp_axis
                    break
        if client_axis is not None:
            entries[0] = client_axis
        specs.append(P(*entries))
    return jax.tree_util.tree_unflatten(treedef, specs)


def _base_ndim(name: str, ndim: int, client_axis) -> int:
    if name in _RULES:
        return len(_RULES[name])
    # replicated leaves: assume all leading dims are stack/client dims except
    # the last (the feature dim); scalars pass through.
    return min(ndim, 1)


def feasible_specs(specs, shapes, mesh):
    """Drop spec entries whose dim is not divisible by the mesh-axis product.

    pjit in_shardings (unlike constraints) hard-fail on non-divisible dims
    (e.g. vocab 92553 on a 16-way model axis) — those leaves degrade to
    replicated on that dim. Real deployments pad such dims; we keep the
    assigned configs exact and record the replication in DESIGN.md.
    """
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))

    def fix(spec, leaf):
        shape = leaf.shape
        entries = list(tuple(spec)) + [None] * (len(shape) - len(tuple(spec)))
        out = []
        for dim, e in zip(shape, entries):
            if e is None:
                out.append(None)
                continue
            axes = e if isinstance(e, (tuple, list)) else (e,)
            tot = 1
            for a in axes:
                tot *= sizes.get(a, 1)
            out.append(e if dim % tot == 0 else None)
        return P(*out)

    return jax.tree.map(fix, specs, shapes,
                        is_leaf=lambda s: isinstance(s, P))


def scatter_dim(shape, n: int) -> Optional[int]:
    """The dimension of an array to reduce-scatter over ``n`` devices, or
    None where the TPU compiler would not emit a reduce-scatter.

    A float32 array is tiled (8, 128) over its last two dimensions, and a
    shard the compiler cannot lay out in whole tiles falls back to an
    all-reduce and a slice: so the rows must fill a tile, and the leading
    (untiled) dimension is scattered where it divides by ``n`` (the layer
    axis of a stacked leaf), else the columns where each shard keeps whole
    128-lane tiles (an embedding's width). A 2-D array's rows are never
    scattered: the compiler pads them and falls back too.
    """
    if len(shape) < 2 or shape[-2] < 8:
        return None
    for d in range(len(shape) - 2):
        if shape[d] % n == 0:
            return d
    if shape[-1] % (128 * n) == 0:
        return len(shape) - 1
    return None


# ---------------------------------------------------------------------------
# KV / recurrent cache specs (serving)
# ---------------------------------------------------------------------------

_CACHE_RULES = {
    # name -> (base_ndim, spec). Batch dim on data(+pod); heads on model.
    "k": (4, (("data",), None, "model", None)),        # (B, C, KV, hd)
    "v": (4, (("data",), None, "model", None)),
    "ckv": (3, (("data",), None, None)),               # MLA latent (B, C, r)
    "k_rope": (3, (("data",), None, None)),
    "conv": (3, (("data",), None, "model")),           # (B, K-1, ch)
}


def cache_specs(cache, data_axes=("data",), seq_axes=()):
    """PartitionSpec tree for a decode cache pytree (leading stack dims ok).

    ``data_axes`` shard the batch dim; ``seq_axes`` (mutually exclusive in
    practice — used when batch is too small, e.g. long_500k b=1) shard the
    cache sequence dim of k/v/ckv/k_rope buffers.
    """
    data_axes = tuple(data_axes)
    seq_axes = tuple(seq_axes)
    bspec = data_axes if data_axes else None
    sspec = seq_axes if seq_axes else None
    flat, treedef = jax.tree_util.tree_flatten_with_path(cache)
    specs = []
    for path, leaf in flat:
        name = None
        for entry in reversed(path):
            if isinstance(entry, jax.tree_util.DictKey):
                name = entry.key
                break
        if name == "pos":
            specs.append(P())
            continue
        if name == "state":
            # mamba2 (B,H,P,N) vs rglru (B,lru): dispatch on trailing ndim
            base = (bspec, "model", None, None) if leaf.ndim >= 4 \
                else (bspec, "model")
            base_nd = len(base)
        elif name in ("k", "v"):
            base_nd, base = 4, (bspec, sspec, "model", None)
        elif name in ("k_scale", "v_scale"):
            base_nd, base = 3, (bspec, sspec, "model")
        elif name in ("ckv", "k_rope"):
            base_nd, base = 3, (bspec, sspec, None)
        elif name == "conv":
            base_nd, base = 3, (bspec, None, "model")
        else:
            base_nd, base = leaf.ndim, (None,) * leaf.ndim
        extra = leaf.ndim - base_nd
        specs.append(P(*(((None,) * extra) + tuple(base))))
    return jax.tree_util.tree_unflatten(treedef, specs)
