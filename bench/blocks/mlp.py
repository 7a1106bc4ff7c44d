"""SwiGLU feed-forward sub-layer: ``x + W_down(silu(W_gate h) * W_up h)``
with ``h = rms_norm(x, ln2)``. Plain float32 reference and its
operations."""
from __future__ import annotations

import jax

from bench.blocks.common import rms_norm


def apply(p, x, cfg, mm):
    m = p["mlp"]
    h = rms_norm(x, p["ln2"], cfg["norm_eps"])
    g = mm("bsd,df->bsf", h, m["w_gate"])
    u = mm("bsd,df->bsf", h, m["w_up"])
    return x + mm("bsf,fd->bsd", jax.nn.silu(g) * u, m["w_down"])


def param_shapes(cfg):
    d, f, w = cfg["d_model"], cfg["d_ff"], cfg["dtype"]
    return {"ln2": ((d,), w), "mlp": {"w_gate": ((d, f), w), "w_up": ((d, f), w),
                                      "w_down": ((f, d), w)}}


def matmul_params(cfg) -> int:
    return 3 * cfg["d_model"] * cfg["d_ff"]


def mixer_flops(cfg, seq_len: int) -> float:
    return 0.0
