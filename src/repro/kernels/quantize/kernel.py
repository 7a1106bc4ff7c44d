"""Fused quantize / dequantize-accumulate — Pallas kernels.

The compressed communication round has two memory-bound halves:

  * client side: scale + stochastic-round + clip + narrow-cast of the local
    model (f32 -> int8 codes). Unfused, XLA materialises the scaled f32
    intermediate and the U[0,1) floats; the kernel streams x and the raw
    uint32 bits through VMEM once and writes codes directly.

  * server side: dequantize N client messages and reduce them to the
    consensus mean. Fused, each int8 tile is read once, widened in-register,
    weighted by its client scale and accumulated — no (N, M) f32
    intermediate ever hits HBM.

Tiling mirrors ``fused_update``: flat 1-D view, 128-lane blocks. Random bits
are *passed in* (jax.random outside, viewed as int32) rather than drawn from
the on-core PRNG so the kernel is deterministic, CPU-interpretable, and
bit-exact against ``ref.py``, whose ``uniform_from_bits`` both use. int8
TPU tiles want (32, 128) alignment; the flat view is padded to the block
size so compiled mode sees aligned tiles.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.quantize.ref import uniform_from_bits

# int8 arrays tile as (32, 128) on TPU (4× the f32 (8, 128) sublane
# packing). Interpret mode happily runs any block shape, which would let
# misaligned tilings hide until a compiled run — the kernels therefore
# refuse blocks that don't pad the flat (rows, 128) view to whole tiles.
# Single validator for every entry point (ops.py re-exports it).
INT8_TILE = (32, 128)


def check_tile_alignment(block: int) -> int:
    """Validate a flat block size against the int8 (32, 128) TPU tile.

    The kernels view their operands as (block/128, 128) lane blocks; int8
    stores want the row count to be a multiple of 32, so ``block`` must be
    a positive multiple of 32·128 = 4096 elements. Returns ``block``.
    """
    tile = INT8_TILE[0] * INT8_TILE[1]
    if block <= 0 or block % tile:
        raise ValueError(
            f"quantize kernels tile int8 as {INT8_TILE}: block={block} must "
            f"be a positive multiple of {tile} so padded inputs land on "
            f"whole tiles (interpret mode would accept it; a compiled TPU "
            f"run would not)")
    return block


def _quant_kernel(x_ref, r_ref, s_ref, q_ref, *, qmax):
    x = x_ref[...].astype(jnp.float32)
    s = s_ref[0, 0]
    y = x / s * qmax
    q = jnp.floor(y + uniform_from_bits(r_ref[...]))
    q_ref[...] = jnp.clip(q, -qmax, qmax).astype(jnp.int8)


def quantize_kernel(x, rand_bits, scale, *, bits: int = 8,
                    block: int = 65536, interpret: bool = False):
    """x: any-shape f32; rand_bits: uint32 same shape; scale: () f32.

    Returns int8 codes, same shape as x.
    """
    qmax = float(2 ** (bits - 1) - 1)
    shape, n = x.shape, x.size
    pad = (-n) % check_tile_alignment(block)
    flat = lambda a: jnp.pad(a.reshape(-1), (0, pad)).reshape(-1, 128)
    rows = (n + pad) // 128
    brows = block // 128

    q = pl.pallas_call(
        functools.partial(_quant_kernel, qmax=qmax),
        grid=(rows // brows,),
        in_specs=[pl.BlockSpec((brows, 128), lambda i: (i, 0)),
                  pl.BlockSpec((brows, 128), lambda i: (i, 0)),
                  pl.BlockSpec((1, 1), lambda i: (0, 0))],
        out_specs=pl.BlockSpec((brows, 128), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, 128), jnp.int8),
        interpret=interpret,
    )(flat(x.astype(jnp.float32)),
      flat(jax.lax.bitcast_convert_type(rand_bits, jnp.int32)),
      jnp.asarray(scale, jnp.float32).reshape(1, 1))
    return q.reshape(-1)[:n].reshape(shape)


def _deq_kernel(q_ref, s_ref, o_ref, *, qmax, inv_n):
    q = q_ref[...].astype(jnp.float32)          # (N, brows, 128)
    w = (s_ref[...].astype(jnp.float32) / qmax)  # (N, 1)
    o_ref[...] = jnp.sum(q * w[:, :, None], axis=0) * inv_n


def dequant_mean_kernel(q, scales, *, bits: int = 8, block: int = 65536,
                        interpret: bool = False):
    """q: (N, ...) int8 codes; scales: (N,) f32. Returns f32 mean of q[0]'s shape."""
    qmax = float(2 ** (bits - 1) - 1)
    N = q.shape[0]
    shape = q.shape[1:]
    n = q[0].size
    pad = (-n) % check_tile_alignment(block)
    qf = jnp.pad(q.reshape(N, -1), ((0, 0), (0, pad))).reshape(N, -1, 128)
    rows = (n + pad) // 128
    brows = block // 128

    out = pl.pallas_call(
        functools.partial(_deq_kernel, qmax=qmax, inv_n=1.0 / N),
        grid=(rows // brows,),
        in_specs=[pl.BlockSpec((N, brows, 128), lambda i: (0, i, 0)),
                  pl.BlockSpec((N, 1), lambda i: (0, 0))],
        out_specs=pl.BlockSpec((brows, 128), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, 128), jnp.float32),
        interpret=interpret,
    )(qf, scales.astype(jnp.float32).reshape(N, 1))
    return out.reshape(-1)[:n].reshape(shape)
