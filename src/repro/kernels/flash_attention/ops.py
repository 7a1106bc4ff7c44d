"""Jit'd public wrapper for the flash-attention kernel.

``flash_attention(..., impl=)``, ``impl`` required:
  * "pallas"     — TPU kernel (deploy target)
  * "interpret"  — same kernel body executed in Python on CPU (validation)
  * "xla"        — the pure-jnp oracle (ref.py)

A recompute-based custom VJP makes the kernel trainable without a handwritten
backward: the forward uses the kernel, the backward differentiates the oracle
(identical math, checked by tests to ~1e-6).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention import kernel as K
from repro.kernels.flash_attention import ref as R


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash(q, k, v, causal, window, softcap, scale, interpret):
    return K.flash_attention(q, k, v, causal=causal, window=window,
                             softcap=softcap, scale=scale, interpret=interpret)


def _flash_fwd(q, k, v, causal, window, softcap, scale, interpret):
    out = _flash(q, k, v, causal, window, softcap, scale, interpret)
    return out, (q, k, v)


def _flash_bwd(causal, window, softcap, scale, interpret, res, g):
    q, k, v = res

    def oracle(q, k, v):
        return R.attention_ref(q, k, v, causal=causal, window=window,
                               softcap=softcap, scale=scale).astype(q.dtype)

    _, vjp = jax.vjp(oracle, q, k, v)
    return vjp(g)


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(q, k, v, *, causal: bool = True,
                    window: Optional[int] = None,
                    softcap: Optional[float] = None,
                    scale: Optional[float] = None,
                    impl: str):
    if impl == "xla":
        return R.attention_ref(q, k, v, causal=causal, window=window,
                               softcap=softcap, scale=scale).astype(q.dtype)
    return _flash(q, k, v, causal, window, softcap, scale, impl == "interpret")
