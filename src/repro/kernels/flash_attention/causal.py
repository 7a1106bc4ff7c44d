"""Full-sequence causal self-attention: JAX's splash kernel on TPU.

``causal_attention(q, k, v, xla, scale=)`` runs the Pallas splash-attention
kernel that ships with JAX (``jax.experimental.pallas.ops.tpu.
splash_attention``: fused forward, dQ and dKV kernels that skip the blocks
above the diagonal) when the program is lowered for a TPU, and ``xla(q, k,
v)`` on every other platform. The choice is made at lowering
(``jax.lax.platform_dependent``), not from the process's default backend:
a compile for a described TPU from a CPU process takes the kernel, and a
CPU program lowers ``xla`` alone.

Scores, probabilities and their gradients live in VMEM tiles only. The
kernel feeds q, k, v and dS to the MXU in the inputs' dtype (bfloat16 in
training) with float32 accumulation, and keeps the running max, the
normalizer and dS in float32. Its forward P·V takes P and V as float32.

The kernel cannot be partitioned by XLA. ``kernel_mesh(mesh)`` names the
mesh of the program being traced (``core.local_sgd.build_train_steps``
sets it around its local step); the kernel then runs under
``jax.shard_map`` with every operand replicated, so each device attends
over the sequences it holds and no collective is added.

Each attention lowered counts once in ``repro.obs.metrics.registry()``
under ``attention.lowered{path=kernel|xla}``.
"""
from __future__ import annotations

import contextlib
import contextvars
from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax.experimental.pallas.ops.tpu import splash_attention as splash
from jax.sharding import PartitionSpec as P

from repro.obs.lowered import lowering_counter

_BLOCKS = (512, 256, 128)   # tile edges tried, largest first

_MESH: contextvars.ContextVar = contextvars.ContextVar(
    "attention_kernel_mesh", default=None)

_lowered = lowering_counter(
    "attention.lowered",
    help="full-sequence causal attentions lowered, by path")


@contextlib.contextmanager
def kernel_mesh(mesh):
    """Trace under ``mesh``: the kernel runs under ``shard_map`` on it."""
    token = _MESH.set(mesh)
    try:
        yield
    finally:
        _MESH.reset(token)


def block_size(seq_len: int) -> Optional[int]:
    """The kernel's tile edge for ``seq_len``, or None if none divides it."""
    return next((b for b in _BLOCKS if seq_len % b == 0), None)


def splash_causal(q, k, v, *, scale: float, interpret: bool = False):
    """The splash kernel alone. q, k: (B, S, H, dqk), v: (B, S, H, dv)."""
    B, S, H, _ = q.shape
    b = block_size(S)
    sizes = splash.BlockSizes(
        block_q=b, block_kv=b, block_kv_compute=b,
        block_q_dkv=b, block_kv_dkv=b, block_kv_dkv_compute=b,
        block_q_dq=b, block_kv_dq=b)
    mask = splash.MultiHeadMask([splash.CausalMask((S, S))] * H)
    kernel = splash.make_splash_mha(mask, block_sizes=sizes, head_shards=1,
                                    q_seq_shards=1, interpret=interpret)
    heads = lambda x: jnp.swapaxes(x, 1, 2)          # (B, H, S, d)
    q = (q.astype(jnp.float32) * scale).astype(q.dtype)
    out = jax.vmap(kernel)(heads(q), heads(k), heads(v))
    return heads(out)


def causal_attention(q, k, v, xla: Callable, *, scale: float):
    """softmax(q·kᵀ·scale + causal mask)·v: the kernel on TPU, else ``xla``.

    q, k: (B, S, H, dqk), v: (B, S, H, dv) with S a multiple of
    ``block_size``; returns (B, S, H, dv) in q's dtype.
    """
    mesh = _MESH.get()

    def kernel(q, k, v):
        fn = lambda q, k, v: splash_causal(q, k, v, scale=scale)
        if mesh is not None:
            # the kernel's outputs carry no varying-axes type to check
            fn = jax.shard_map(fn, mesh=mesh, in_specs=P(), out_specs=P(),
                               check_vma=False)
        return _lowered(fn(q, k, v), path="kernel")

    return jax.lax.platform_dependent(
        q, k, v, tpu=kernel,
        default=lambda q, k, v: _lowered(xla(q, k, v), path="xla"))
