"""Oracles for the communication-compression kernels.

``quantize_ref``: symmetric linear quantization to ``bits``-bit signed codes
with *stochastic rounding* — the client-side half of a compressed
communication round (comm.QuantizedMean):

    qmax = 2^(bits-1) - 1
    y    = x / scale * qmax
    q    = clip(floor(y + u), -qmax, qmax)        u ~ U[0,1) from rand_bits

Stochastic rounding keeps the quantizer unbiased (E[q·scale/qmax] = x), which
is what the error-feedback convergence argument needs.

``dequant_mean_ref``: the server-side half — dequantize N client messages and
average them in one pass:

    mean = (1/N) Σ_i q_i · (scale_i / qmax)

Both are written with the *same* op order as the Pallas kernels so
ops-vs-ref parity is bit-exact given the same random bits.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

_INV_2_32 = 1.0 / 4294967296.0  # uint32 bits -> U[0,1)


def uniform_from_bits(bits):
    """Random bits -> U[0,1) f32: ``float32(bits) * 2^-32``.

    ``bits`` is uint32, or the same bits viewed as int32 (what the Pallas
    kernel receives: the TPU kernel compiler has no uint32 -> f32 cast).
    The 16-bit halves are exact in f32 and IEEE addition rounds their
    exact sum once, so the result equals the direct cast bit for bit.
    """
    b = jax.lax.bitcast_convert_type(bits, jnp.int32)
    hi = jax.lax.shift_right_logical(b, 16).astype(jnp.float32)
    lo = (b & 0xFFFF).astype(jnp.float32)
    return (hi * 65536.0 + lo) * _INV_2_32


def qmax_for(bits: int) -> float:
    return float(2 ** (bits - 1) - 1)


def quantize_ref(x, rand_bits, scale, *, bits: int = 8):
    """x: f32 array; rand_bits: uint32, same shape; scale: scalar f32 (>0).

    Returns int8 codes in [-qmax, qmax].
    """
    qmax = qmax_for(bits)
    y = x.astype(jnp.float32) / scale * qmax
    q = jnp.floor(y + uniform_from_bits(rand_bits))
    return jnp.clip(q, -qmax, qmax).astype(jnp.int8)


def dequant_mean_ref(q, scales, *, bits: int = 8):
    """q: (N, ...) int8 codes; scales: (N,) f32. Returns f32 mean, shape q[0]."""
    qmax = qmax_for(bits)
    n = q.shape[0]
    w = (scales.astype(jnp.float32) / qmax).reshape((n,) + (1,) * (q.ndim - 1))
    return jnp.sum(q.astype(jnp.float32) * w, axis=0) * (1.0 / n)
