"""Pieces every reference block shares: norm, rotary embedding, pair count."""
from __future__ import annotations

import jax.numpy as jnp


def rms_norm(x, scale, eps):
    """RMS norm with a zero-centred gain: ``x / rms(x) * (1 + scale)``."""
    x = x.astype(jnp.float32)
    ms = jnp.mean(x * x, axis=-1, keepdims=True)
    return x / jnp.sqrt(ms + eps) * (1.0 + scale.astype(jnp.float32))


def rope(x, pos, theta):
    """Rotary embedding on the last axis, rotating its two halves
    (x[:h], x[h:]) as complex pairs. x: (B, S, ..., hd); pos: (S,)."""
    hd = x.shape[-1]
    inv = theta ** (-jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = pos.astype(jnp.float32)[:, None] * inv[None, :]          # (S, hd/2)
    ang = ang.reshape((1, ang.shape[0]) + (1,) * (x.ndim - 3) + (hd // 2,))
    cos = jnp.concatenate([jnp.cos(ang)] * 2, axis=-1)
    sin = jnp.concatenate([jnp.sin(ang)] * 2, axis=-1)
    half = hd // 2
    rotated = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
    return x * cos + rotated * sin


def causal_pairs(seq_len: int) -> float:
    """(query, key) pairs with key <= query in one sequence."""
    return seq_len * (seq_len + 1) / 2.0
