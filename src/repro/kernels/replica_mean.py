"""Average the client replicas of a leaf in one in-place pass: a Pallas kernel.

With every client's replica of a leaf on one device (the leaf stacked
clients first, ``(n, ...)``), the round's end is one read and one write of
the leaf. XLA lowers ``jnp.mean`` + ``broadcast_to`` to four passes (a
float32 reduce, the divide, the cast to the leaf's dtype and an n-way
broadcast). ``replica_mean`` reads each block of all n replicas once, sums
them in float32 from the first replica to the last, divides by n, rounds to
the leaf's dtype once and writes that mean into every replica in place
(``input_output_aliases``; with the state donated no copy is made).

The kernel views each leaf's two minor axes as its tiles. Where the last
axis is not a multiple of 128 lanes and the second-to-last is, TPU keeps the
leaf with its last two axes swapped in memory (``{..., 2, 3, 1, 0}``); the
kernel takes the swapped view, which is a bitcast there, where the
row-major view would cost two relayout copies of the leaf. ``kernel_view``
says which view a leaf takes, or that it tiles neither way and stays on
XLA's ops. Leading axes (layers stacked) are grid axes.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

LANES = 128
BLOCK_BYTES = 1 << 20   # one block of all n replicas; four are in VMEM


def _sublanes(dtype) -> int:
    """Rows of one TPU tile: 8 for float32, 16 for bfloat16."""
    return 32 // jnp.dtype(dtype).itemsize


def kernel_view(shape, dtype) -> Optional[bool]:
    """How ``replica_mean`` views a leaf of ``shape`` (clients first):
    ``False`` as it is, ``True`` with its last two axes swapped, ``None``
    where its two minor axes tile neither way (too few rows, or no axis of
    whole 128 lanes) or its dtype is not a float of at most 32 bits."""
    dtype = jnp.dtype(dtype)
    if (len(shape) < 3 or not jnp.issubdtype(dtype, jnp.floating)
            or dtype.itemsize > 4):
        return None
    rows, lanes = shape[-2:]
    swap = lanes % LANES != 0
    if swap:
        rows, lanes = lanes, rows
    if lanes % LANES or rows % _sublanes(dtype):
        return None
    return swap


def _block(n: int, rows: int, lanes: int, dtype) -> tuple[int, int]:
    """Rows and lanes of a block that holds about ``BLOCK_BYTES`` of all
    ``n`` replicas: whole rows where a tile's rows fit, else whole tiles."""
    sub, size = _sublanes(dtype), jnp.dtype(dtype).itemsize
    row_bytes = n * lanes * size
    if sub * row_bytes <= BLOCK_BYTES:
        return min(rows, max(sub, BLOCK_BYTES // row_bytes // sub * sub)), lanes
    return sub, max(LANES, BLOCK_BYTES // (n * sub * size) // LANES * LANES)


def _mean_kernel(x_ref, o_ref):
    n = x_ref.shape[0]
    total = x_ref[0].astype(jnp.float32)
    for i in range(1, n):
        total = total + x_ref[i].astype(jnp.float32)
    # a power of two's reciprocal is exact, so the product is the quotient
    # (and the multiply XLA makes of that divide)
    mean = total * (1.0 / n) if n & (n - 1) == 0 else total / n
    mean = mean.astype(o_ref.dtype)
    for i in range(n):
        o_ref[i] = mean


def replica_mean(x, *, interpret: bool = False):
    """``x`` (clients on axis 0) with every replica replaced by the
    replicas' mean: the float32 sum of the n values, first to last, divided
    by n and rounded to ``x``'s dtype once. ``x`` must have a
    ``kernel_view``; the result reuses its buffer."""
    swap = kernel_view(x.shape, x.dtype)
    if swap is None:
        raise ValueError(f"a leaf of shape {x.shape} and dtype {x.dtype} "
                         f"does not tile: average it with XLA's ops")
    if swap:
        x = jnp.swapaxes(x, -1, -2)
    n, *lead, rows, lanes = x.shape
    br, bl = _block(n, rows, lanes, x.dtype)
    spec = pl.BlockSpec(
        (n, *[pl.squeezed] * len(lead), br, bl),
        lambda *g: (0, *g))
    out = pl.pallas_call(
        _mean_kernel,
        grid=(*lead, pl.cdiv(rows, br), pl.cdiv(lanes, bl)),
        in_specs=[spec], out_specs=spec,
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        input_output_aliases={0: 0},
        name="replica_mean",
        interpret=interpret,
    )(x)
    return jnp.swapaxes(out, -1, -2) if swap else out
