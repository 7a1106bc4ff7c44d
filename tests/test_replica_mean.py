"""The round's mean of co-located replicas in one in-place pass
(``kernels/replica_mean.py``, ``local_sgd._average_in_place``), in Pallas
interpret mode on the CPU: bit for bit ``end_round``'s
``tree_mean_leading`` + ``tree_broadcast_leading`` at two clients, the
left-to-right float32 sum divided by n and rounded once at three and
four, and counted as ``sync.lowered{path=colocated}`` only for a dense
blocking star whose replicas share a device.

Blocks are shrunk to a few KiB so that small leaves still span many
blocks, partial ones at their edges included.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import local_sgd as LS
from repro.core.round import end_round
from repro.kernels import replica_mean as RM
from repro.obs import metrics as obs_metrics
from repro.utils.tree import tree_mean_leading

SMALL_BLOCK = 48 * 1024

# per-replica shapes: layers of a matrix whose last axis is no whole number
# of lanes (the swapped view), an embedding-like matrix, a matrix of many
# lanes (blocked over lanes too), a norm stack and a conv stack with too
# few rows, and a vector (all three stay on XLA's ops)
LEAVES = {"stack": (3, 256, 336), "embed": (208, 384), "wide": (16, 8192),
          "norm": (4, 256), "conv": (3, 4, 384), "vec": (256,)}
TILED = {"stack": True, "embed": False, "wide": False,
         "norm": None, "conv": None, "vec": None}


def _state(n, seed=0):
    key = jax.random.key(seed)
    leaf = lambda i, s, dt: (3 * jax.random.normal(
        jax.random.fold_in(key, i), (n,) + s)).astype(dt)
    return {"params": {k: leaf(i, s, jnp.bfloat16)
                       for i, (k, s) in enumerate(LEAVES.items())},
            "opt": {"mu": {k: leaf(10 + i, s, jnp.float32)
                           for i, (k, s) in enumerate(LEAVES.items())}},
            "step": jnp.zeros((), jnp.int32)}


def _bits_equal(a, b):
    a, b = np.asarray(a), np.asarray(b)
    return a.dtype == b.dtype and np.array_equal(a.view(np.uint8),
                                                 b.view(np.uint8))


@pytest.fixture(scope="module")
def kernel_round():
    """The two-client round of ``build_sync_step()`` with its TPU branch
    taken and the kernel interpreted, and ``end_round``'s of the same
    state."""
    from functools import partial

    state = _state(2)
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(RM, "BLOCK_BYTES", SMALL_BLOCK)
        mp.setattr(LS, "replica_mean", partial(RM.replica_mean,
                                               interpret=True))
        mp.setattr(jax.lax, "platform_dependent",
                   lambda *args, tpu, default: tpu(*args))
        out = jax.jit(LS.build_sync_step())(state)
    params, opt = end_round(tree_mean_leading(state["params"]),
                            state["opt"], 2)
    return out, {"params": params, "opt": opt}


@pytest.mark.parametrize("part", ["params", "opt"])
@pytest.mark.parametrize("name", list(LEAVES))
def test_kernel_round_equals_end_round_bit_for_bit(kernel_round, part, name):
    """At two clients every leaf of the kernel's round, bfloat16 params
    and float32 momentum, swapped view, row-major view or fallen back to
    XLA, equals ``end_round``'s mean and broadcast bit for bit."""
    out, ref = kernel_round
    get = (lambda t: t[part][name]) if part == "params" else (
        lambda t: t[part]["mu"][name])
    assert get(out).dtype == (jnp.bfloat16 if part == "params"
                              else jnp.float32)
    assert _bits_equal(get(out), get(ref)), (part, name)


@pytest.mark.parametrize("name", [k for k, v in TILED.items()
                                  if v is not None])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
@pytest.mark.parametrize("n", [3, 4])
def test_replica_mean_is_the_left_to_right_sum_over_n(monkeypatch, name,
                                                      dtype, n):
    """At three and four clients each replica becomes the float32 sum of
    the n replicas, first to last, divided by n (as XLA compiles that
    divide) and rounded to the leaf's dtype once."""
    monkeypatch.setattr(RM, "BLOCK_BYTES", SMALL_BLOCK)
    x = (3 * jax.random.normal(jax.random.key(n), (n,) + LEAVES[name])
         ).astype(dtype)

    @jax.jit
    def ref(x):
        total = x[0].astype(jnp.float32)
        for i in range(1, n):
            total = total + x[i].astype(jnp.float32)
        return jnp.broadcast_to((total / n).astype(dtype)[None], x.shape)

    got = jax.jit(lambda x: RM.replica_mean(x, interpret=True))(x)
    assert _bits_equal(got, ref(x))


@pytest.mark.parametrize("shape,dtype,view", [
    ((2, 4, 2560, 288), jnp.bfloat16, True),       # MLA's w_dkv stack
    ((2, 4, 2560, 288), jnp.float32, True),
    ((2, 8, 2560, 10576), jnp.bfloat16, True),     # Mamba2's w_in stack
    ((2, 8, 2560, 10576), jnp.float32, True),
    ((2, 4, 2560, 6400), jnp.bfloat16, False),     # MLP stacks
    ((2, 73472, 2560), jnp.bfloat16, False),       # MiniCPM3's embedding
    ((2, 50432, 2560), jnp.float32, False),        # Mamba2's, momentum
    ((2, 8, 4, 5376), jnp.bfloat16, None),         # Mamba2's conv_w
    ((2, 8, 80), jnp.float32, None),               # A_log, D, dt_bias
    ((2, 4, 2560), jnp.bfloat16, None),            # norm gains
    ((2, 8, 2560), jnp.bfloat16, None),            # 8 rows: half a tile
    ((2, 8, 2560), jnp.float32, False),            # one whole float32 tile
    ((2, 2560), jnp.bfloat16, None),               # final_norm
    ((2, 4, 256, 128), jnp.int32, None),           # not a float
])
def test_kernel_view_by_shape(shape, dtype, view):
    """Which view the kernel takes of a stacked leaf: swapped where the
    last axis is no whole number of lanes and the one before is; none
    where the two minor axes do not tile or the dtype is no float of at
    most 32 bits."""
    assert RM.kernel_view(shape, dtype) is view


def test_replica_mean_refuses_a_leaf_that_does_not_tile():
    with pytest.raises(ValueError, match="does not tile"):
        RM.replica_mean(jnp.zeros((2, 4, 256), jnp.bfloat16), interpret=True)


def _one_device_mesh():
    return jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)


@pytest.mark.parametrize("kwargs,mesh,path", [
    ({}, None, "colocated"),
    ({}, "1x1", "colocated"),
    ({"reducer": "dense"}, None, "colocated"),
    ({"streaming": True}, None, "all_reduce"),
    ({"reducer": "int8"}, None, "all_reduce"),
    ({"hierarchical": True, "inter_reducer": "dense"}, None, "all_reduce"),
])
def test_colocated_counts_only_the_dense_star(kwargs, mesh, path):
    """``sync.lowered{path=colocated}`` moves once for a dense blocking
    star whose replicas share a device (no mesh, or a mesh of one
    device); streaming, compressed and two-level rounds count
    ``all_reduce``. On the CPU the round lowers to ``end_round``'s ops
    either way, so it is the replicas' mean."""
    reg = obs_metrics.registry()
    paths = ("colocated", "all_reduce", "scatter_gather")
    count = lambda p: (reg["sync.lowered"].value(path=p)
                       if "sync.lowered" in reg else 0.0)
    # four clients: two pods of two for the two-level round
    state = _state(4)
    before = {p: count(p) for p in paths}
    sync = LS.build_sync_step(
        mesh=_one_device_mesh() if mesh else None, **kwargs)
    out = jax.jit(sync)(state)
    assert {p: count(p) - before[p] for p in paths} == {
        p: float(p == path) for p in paths}
    if path == "colocated":
        mean = tree_mean_leading(state["params"])
        for a, m in zip(jax.tree.leaves(out["params"]),
                        jax.tree.leaves(mean)):
            assert _bits_equal(a, jnp.broadcast_to(m[None], a.shape))
