"""Multi-head latent attention sub-layer (MiniCPM3 / DeepSeek-V2 style).

Plain float32 reference of ``x + MLA(rms_norm(x, ln1))`` as the
configuration states it, written without the program's code, and the
operations the sub-layer requires.

Reads the layer's ``ln1`` and ``attn`` weights:
``w_dq (d, q_lora)``, ``q_norm``, ``w_uq (q_lora, H*(nope+rope))``,
``w_dkv (d, kv_lora+rope)``, ``kv_norm``, ``w_uk (kv_lora, H*nope)``,
``w_uv (kv_lora, H*v)``, ``wo (H*v, d)``.
"""
from __future__ import annotations

import math

import jax.numpy as jnp

from bench.blocks.common import causal_pairs, rms_norm, rope


def apply(p, x, cfg, mm):
    att = cfg["attention"]
    B, S, _ = x.shape
    H = att["n_heads"]
    nope, rd, vd = att["qk_nope_head_dim"], att["qk_rope_head_dim"], att["v_head_dim"]
    r = att["kv_lora_rank"]
    a = p["attn"]
    pos = jnp.arange(S)
    h = rms_norm(x, p["ln1"], cfg["norm_eps"])

    cq = rms_norm(mm("bsd,dq->bsq", h, a["w_dq"]), a["q_norm"], cfg["norm_eps"])
    q = mm("bsq,qf->bsf", cq, a["w_uq"]).reshape(B, S, H, nope + rd)
    q_nope, q_rope = q[..., :nope], rope(q[..., nope:], pos, att["rope_theta"])

    dkv = mm("bsd,df->bsf", h, a["w_dkv"])
    ckv = rms_norm(dkv[..., :r], a["kv_norm"], cfg["norm_eps"])
    k_rope = rope(dkv[..., r:], pos, att["rope_theta"])          # one per token
    k_nope = mm("bsr,rf->bsf", ckv, a["w_uk"]).reshape(B, S, H, nope)
    v = mm("bsr,rf->bsf", ckv, a["w_uv"]).reshape(B, S, H, vd)

    scores = (mm("bqhn,bkhn->bhqk", q_nope, k_nope)
              + mm("bqhr,bkr->bhqk", q_rope, k_rope)) / math.sqrt(nope + rd)
    causal = pos[None, :] <= pos[:, None]
    scores = jnp.where(causal, scores, -jnp.inf)
    w = jnp.exp(scores - jnp.max(scores, axis=-1, keepdims=True))
    w = w / jnp.sum(w, axis=-1, keepdims=True)
    o = mm("bhqk,bkhv->bqhv", w, v).reshape(B, S, H * vd)
    return x + mm("bsf,fd->bsd", o, a["wo"])


def param_shapes(cfg):
    """The sub-layer's weights as the program lays them out: (shape, dtype)."""
    att, d, w = cfg["attention"], cfg["d_model"], cfg["dtype"]
    H, qk = att["n_heads"], att["qk_nope_head_dim"] + att["qk_rope_head_dim"]
    r, ql = att["kv_lora_rank"], att["q_lora_rank"]
    return {"ln1": ((d,), w), "attn": {
        "w_dq": ((d, ql), w), "q_norm": ((ql,), w), "w_uq": ((ql, H * qk), w),
        "w_dkv": ((d, r + att["qk_rope_head_dim"]), w), "kv_norm": ((r,), w),
        "w_uk": ((r, H * att["qk_nope_head_dim"]), w),
        "w_uv": ((r, H * att["v_head_dim"]), w),
        "wo": ((H * att["v_head_dim"], d), w)}}


def matmul_params(cfg) -> int:
    att, d = cfg["attention"], cfg["d_model"]
    H, qk = att["n_heads"], att["qk_nope_head_dim"] + att["qk_rope_head_dim"]
    r, ql = att["kv_lora_rank"], att["q_lora_rank"]
    return (d * ql + ql * H * qk + d * (r + att["qk_rope_head_dim"])
            + r * H * att["qk_nope_head_dim"] + r * H * att["v_head_dim"]
            + H * att["v_head_dim"] * d)


def mixer_flops(cfg, seq_len: int) -> float:
    """Forward FLOPs of the causal score and value products of one
    sequence: every (query, key) pair with key <= query, once."""
    att = cfg["attention"]
    per_pair = (att["qk_nope_head_dim"] + att["qk_rope_head_dim"]
                + att["v_head_dim"])
    return 2.0 * att["n_heads"] * causal_pairs(seq_len) * per_pair
