"""Mamba2 sub-layer (SSD, arXiv:2405.21060): ``x + Mamba2(rms_norm(x, ln1))``.

The reference evaluates the state-space recurrence in its plain
quadratic form, every output position against every earlier input,

    y_t = sum_{s<=t} (C_t . B_s) exp(sum_{s<k<=t} dt_k A) dt_s x_s + D x_t,

in float32: independent of the chunked form the program runs. The
operation count is that of the chunked form (the work the layer needs
at its chunk size), with the causal half of each chunk.

Reads the layer's ``ln1`` and ``mamba`` weights: ``w_in (d, 2*d_inner +
2*N + H)`` split as z, x, B, C, dt; ``conv_w (K, d_inner + 2*N)``;
``A_log``, ``D``, ``dt_bias (H,)``; ``ssm_norm (d_inner,)``;
``w_out_ssm (d_inner, d)``. One group (G = 1).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bench.blocks.common import causal_pairs, rms_norm


HEAD_BLOCK = 8   # heads whose (S, S) decay is held at once


def _dims(cfg):
    s = cfg["ssm"]
    d_inner = s["expand"] * cfg["d_model"]
    return d_inner, d_inner // s["head_dim"], s["head_dim"], s["d_state"]


def apply(p, x, cfg, mm):
    m = p["mamba"]
    d_inner, H, P, N = _dims(cfg)
    B, S, _ = x.shape
    h = rms_norm(x, p["ln1"], cfg["norm_eps"])
    proj = mm("bsd,df->bsf", h, m["w_in"])
    z = proj[..., :d_inner]
    xbc = proj[..., d_inner:2 * d_inner + 2 * N]
    dt = proj[..., 2 * d_inner + 2 * N:]

    # depthwise causal convolution: tap i sees the input K-1-i steps back
    w = m["conv_w"].astype(jnp.float32)
    K = w.shape[0]
    conv = sum(w[i] * jnp.pad(xbc, ((0, 0), (K - 1 - i, 0), (0, 0)))[:, :S]
               for i in range(K))
    xbc = jax.nn.silu(conv)
    xs = xbc[..., :d_inner].reshape(B, S, H, P)
    Bm, Cm = xbc[..., d_inner:d_inner + N], xbc[..., d_inner + N:]

    dt = jax.nn.softplus(dt + m["dt_bias"])                      # (B,S,H)
    a = dt * -jnp.exp(m["A_log"])
    cb = mm("btn,bsn->bts", Cm, Bm)
    causal = jnp.arange(S)[None, :] <= jnp.arange(S)[:, None]

    def heads(blk):                       # a block of heads at a time: fits
        a_h, x_h = blk                    # (B,S,h), (B,S,h,P) dt-weighted
        cs = jnp.moveaxis(jnp.cumsum(a_h, axis=1), 1, 2)         # (B,h,S)
        seg = cs[..., :, None] - cs[..., None, :]                # (B,h,t,s)
        decay = jnp.exp(jnp.where(causal, seg, -jnp.inf))
        return mm("bhts,bshp->bthp", decay * cb[:, None], x_h)

    nb = H // HEAD_BLOCK
    split = lambda v: jnp.moveaxis(  # noqa: E731
        v.reshape(v.shape[:2] + (nb, HEAD_BLOCK) + v.shape[3:]), 2, 0)
    y = jax.lax.map(jax.checkpoint(heads), (split(a), split(xs * dt[..., None])))
    y = jnp.moveaxis(y, 0, 2).reshape(B, S, H, P)
    y = y + xs * m["D"][None, None, :, None]
    y = y.reshape(B, S, d_inner) * jax.nn.silu(z)
    y = rms_norm(y, m["ssm_norm"], cfg["norm_eps"])
    return x + mm("bsf,fd->bsd", y, m["w_out_ssm"])


def param_shapes(cfg):
    d, w = cfg["d_model"], cfg["dtype"]
    d_inner, H, _, N = _dims(cfg)
    f32 = "float32"
    return {"ln1": ((d,), w), "mamba": {
        "w_in": ((d, 2 * d_inner + 2 * N + H), w),
        "conv_w": ((cfg["ssm"]["d_conv"], d_inner + 2 * N), w),
        "A_log": ((H,), f32), "D": ((H,), f32), "dt_bias": ((H,), f32),
        "ssm_norm": ((d_inner,), w), "w_out_ssm": ((d_inner, d), w)}}


def matmul_params(cfg) -> int:
    d = cfg["d_model"]
    d_inner, H, _, N = _dims(cfg)
    return d * (2 * d_inner + 2 * N + H) + d_inner * d


def mixer_flops(cfg, seq_len: int) -> float:
    """Forward FLOPs of one sequence through the chunked SSD: the causal
    half of each chunk's C.B and its weighting of x, each chunk's end
    state, the states carried into the next chunk, their read-out, and
    the depthwise convolution."""
    d_inner, H, P, N = _dims(cfg)
    Q = min(cfg["ssm"]["chunk_size"], seq_len)
    nc = seq_len / Q
    pairs = causal_pairs(Q)
    cb = pairs * N
    y_diag = H * pairs * P
    states = H * Q * P * N
    y_off = H * Q * P * N
    carry = H * P * N
    conv = seq_len * (d_inner + 2 * N) * cfg["ssm"]["d_conv"]
    return 2.0 * (nc * (cb + y_diag + states + y_off + carry)) + 2.0 * conv
