"""Attention variants: GQA (qk_norm / softcap / sliding window) and MLA.

Supports three execution modes from one code path:
  * full-sequence (training / prefill) with causal or sliding-window masks,
  * single-token decode against a full KV cache,
  * single-token decode against a ring-buffer (sliding-window) KV cache —
    O(window) state, what makes long_500k lowerable for attention archs.

MLA (DeepSeek-V2 / MiniCPM3) caches the compressed latent + rope key and uses
the *absorbed* formulation at decode time (scores computed in latent space),
the memory-bandwidth-optimal form on TPU.
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig, AttentionConfig
from repro.kernels.flash_attention.causal import block_size, causal_attention
from repro.models.layers import apply_rope, dense_init, rms_norm, softcap
from repro.sharding import shard

NEG_INF = -2.0e38


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------

def init_attention(key, cfg: ArchConfig, dtype):
    att = cfg.attention
    assert att is not None
    d = cfg.d_model
    if att.kind == "gqa":
        k1, k2, k3, k4 = jax.random.split(key, 4)
        p = {
            "wq": dense_init(k1, d, att.n_heads * att.head_dim, dtype),
            "wk": dense_init(k2, d, att.n_kv_heads * att.head_dim, dtype),
            "wv": dense_init(k3, d, att.n_kv_heads * att.head_dim, dtype),
            "wo": dense_init(k4, att.n_heads * att.head_dim, d, dtype),
        }
        if att.qk_norm:
            p["q_norm"] = jnp.zeros((att.head_dim,), dtype)
            p["k_norm"] = jnp.zeros((att.head_dim,), dtype)
        return p
    elif att.kind == "mla":
        keys = jax.random.split(key, 8)
        qk_dim = att.qk_nope_head_dim + att.qk_rope_head_dim
        p = {
            "w_dkv": dense_init(keys[0], d, att.kv_lora_rank + att.qk_rope_head_dim, dtype),
            "kv_norm": jnp.zeros((att.kv_lora_rank,), dtype),
            "w_uk": dense_init(keys[1], att.kv_lora_rank, att.n_heads * att.qk_nope_head_dim, dtype),
            "w_uv": dense_init(keys[2], att.kv_lora_rank, att.n_heads * att.v_head_dim, dtype),
            "wo": dense_init(keys[3], att.n_heads * att.v_head_dim, d, dtype),
        }
        if att.q_lora_rank:
            p["w_dq"] = dense_init(keys[4], d, att.q_lora_rank, dtype)
            p["q_norm"] = jnp.zeros((att.q_lora_rank,), dtype)
            p["w_uq"] = dense_init(keys[5], att.q_lora_rank, att.n_heads * qk_dim, dtype)
        else:
            p["wq"] = dense_init(keys[4], d, att.n_heads * qk_dim, dtype)
        return p
    raise ValueError(att.kind)


# ---------------------------------------------------------------------------
# Mask / core attention
# ---------------------------------------------------------------------------

def _mask_bias(pos_q, pos_k, window: Optional[int]):
    """(Sq, Sk) additive bias: causal (+ sliding window). pos_* are int32 arrays."""
    ok = pos_k[None, :] <= pos_q[:, None]
    if window is not None:
        ok &= pos_k[None, :] > pos_q[:, None] - window
    ok &= pos_k[None, :] >= 0  # ring-buffer slots not yet written carry pos -1
    return jnp.where(ok, 0.0, NEG_INF)


CHUNK_Q_THRESHOLD = 2048  # above this, full-seq attention runs q-chunked
CHUNK_Q = 1024


def attend(q, k, v, bias, cap: Optional[float], scale: float):
    """q: (B,Sq,H,hd) k,v: (B,Sk,KV,hd'), grouped-query without repeating KV.

    For long sequences the q axis is processed in CHUNK_Q blocks under
    lax.scan (flash-style online softmax is unnecessary here since each block
    still sees all of K — the point is never materialising the full (Sq,Sk)
    score tensor). MLA's training attention takes JAX's splash kernel on
    TPU instead (``repro.kernels.flash_attention.causal``).
    """
    B, Sq, H, hd = q.shape
    if Sq > CHUNK_Q_THRESHOLD and Sq % CHUNK_Q == 0:
        nq = Sq // CHUNK_Q
        qb = q.reshape(B, nq, CHUNK_Q, H, hd)
        bb = bias.reshape(nq, CHUNK_Q, bias.shape[-1])

        def body(_, inp):
            qi, bi = inp
            return None, _attend_block(qi, k, v, bi, cap, scale)

        _, out = jax.lax.scan(body, None,
                              (jnp.moveaxis(qb, 1, 0), bb))
        return jnp.moveaxis(out, 0, 1).reshape(B, Sq, H, v.shape[-1])
    return _attend_block(q, k, v, bias, cap, scale)


def _attend_block(q, k, v, bias, cap: Optional[float], scale: float):
    B, Sq, H, hd = q.shape
    KV = k.shape[2]
    G = H // KV
    q = q.reshape(B, Sq, KV, G, hd)
    scores = jnp.einsum("bqkgd,bskd->bkgqs", q.astype(jnp.float32), k.astype(jnp.float32))
    scores = scores * scale
    scores = softcap(scores, cap)
    scores = scores + bias  # bias (Sq, Sk) broadcasts
    w = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgqs,bskd->bqkgd", w, v.astype(jnp.float32))
    return out.reshape(B, Sq, H, v.shape[-1]).astype(q.dtype)


# ---------------------------------------------------------------------------
# GQA apply
# ---------------------------------------------------------------------------

def _split_heads(x, n, hd):
    return x.reshape(x.shape[:-1] + (n, hd))


# ---------------------------------------------------------------------------
# int8 KV-cache quantization (symmetric, per position×head)
# ---------------------------------------------------------------------------

def _quant(x):
    """x: (..., hd) → (int8 values, fp32 scales (...,))."""
    xf = x.astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(xf), axis=-1), 1e-8) / 127.0
    q = jnp.clip(jnp.round(xf / scale[..., None]), -127, 127).astype(jnp.int8)
    return q, scale


def _dequant(q, scale, dtype):
    return (q.astype(jnp.float32) * scale[..., None]).astype(dtype)


def apply_gqa(params, att: AttentionConfig, x, pos_q, *, window, eps,
              cache=None, cache_pos=None, kv_quant=False):
    """x: (B, S, d). pos_q: (S,) absolute positions of the tokens in x.

    cache: None (full-seq) or {"k","v"} buffers (B, C, KV, hd) where C is
    max_len (full cache) or window size (ring buffer). cache_pos: scalar count
    of tokens already in the cache (== absolute position of x[:,0]).
    """
    B, S, d = x.shape
    q = _split_heads(jnp.einsum("bsd,df->bsf", x, params["wq"]), att.n_heads, att.head_dim)
    k = _split_heads(jnp.einsum("bsd,df->bsf", x, params["wk"]), att.n_kv_heads, att.head_dim)
    v = _split_heads(jnp.einsum("bsd,df->bsf", x, params["wv"]), att.n_kv_heads, att.head_dim)
    q = shard(q, None, None, "model", None)
    k = shard(k, None, None, "model", None)
    v = shard(v, None, None, "model", None)
    if att.qk_norm:
        q = rms_norm(q, params["q_norm"], eps)
        k = rms_norm(k, params["k_norm"], eps)
    q = apply_rope(q, pos_q, att.rope_theta)
    k = apply_rope(k, pos_q, att.rope_theta)
    scale = 1.0 / math.sqrt(att.head_dim)

    if cache is None:
        bias = _mask_bias(pos_q, pos_q, window)
        out = attend(q, k, v, bias, att.logit_softcap, scale)
    elif S > 1:
        # prefill: attend over the full in-flight sequence (window-masked),
        # then store the last C positions into the (possibly ring) cache.
        bias = _mask_bias(pos_q, pos_q, window)
        out = attend(q, k, v, bias, att.logit_softcap, scale)
        if kv_quant:
            kq, ks = _quant(k)
            vq, vs = _quant(v)
            cache = {"k": _write_tail(cache["k"], kq),
                     "k_scale": _write_tail_scale(cache["k_scale"], ks),
                     "v": _write_tail(cache["v"], vq),
                     "v_scale": _write_tail_scale(cache["v_scale"], vs)}
        else:
            cache = {"k": _write_tail(cache["k"], k),
                     "v": _write_tail(cache["v"], v)}
    else:
        C = cache["k"].shape[1]
        slot = jnp.mod(cache_pos, C)
        if kv_quant:
            kq, ks = _quant(k)
            vq, vs = _quant(v)
            cache = {
                "k": jax.lax.dynamic_update_slice(cache["k"], kq, (0, slot, 0, 0)),
                "k_scale": jax.lax.dynamic_update_slice(
                    cache["k_scale"], ks, (0, slot, 0)),
                "v": jax.lax.dynamic_update_slice(cache["v"], vq, (0, slot, 0, 0)),
                "v_scale": jax.lax.dynamic_update_slice(
                    cache["v_scale"], vs, (0, slot, 0)),
            }
            kr = _dequant(cache["k"], cache["k_scale"], x.dtype)
            vr = _dequant(cache["v"], cache["v_scale"], x.dtype)
        else:
            cache = {
                "k": jax.lax.dynamic_update_slice(cache["k"], k, (0, slot, 0, 0)),
                "v": jax.lax.dynamic_update_slice(cache["v"], v, (0, slot, 0, 0)),
            }
            kr, vr = cache["k"], cache["v"]
        pos_k = _cache_positions(C, cache_pos)
        bias = _mask_bias(pos_q, pos_k, window)
        out = attend(q, kr, vr, bias, att.logit_softcap, scale)

    out = jnp.einsum("bsf,fd->bsd", out.reshape(B, S, -1), params["wo"])
    return out, cache


def _write_tail(buf, x):
    """Store the last C positions of x (B,S,...) into the cache buffer (B,C,...).

    Prefill-from-zero only. Ring invariant: slot j holds position p with
    p % C == j, so for S > C the tail is rolled by S % C.
    """
    C, S = buf.shape[1], x.shape[1]
    if S >= C:
        tail = x[:, -C:].astype(buf.dtype)
        if S % C:
            tail = jnp.roll(tail, S % C, axis=1)
        return tail
    return jax.lax.dynamic_update_slice(
        buf, x.astype(buf.dtype), (0,) * buf.ndim)


def _cache_positions(C: int, cache_pos):
    """Absolute position held by each of the C cache slots after writing the
    token at ``cache_pos`` into slot ``cache_pos % C`` (ring semantics).

    Slots never written hold -1 (masked out by _mask_bias).
    """
    slots = jnp.arange(C, dtype=jnp.int32)
    cur = jnp.mod(cache_pos, C)
    base = cache_pos - cur  # start of the current ring revolution
    pos = jnp.where(slots <= cur, base + slots, base - C + slots)
    return jnp.where(pos >= 0, pos, -1)


def _write_tail_scale(buf, s):
    """Ring-write for the (B,S,KV) scale tensor (adds/strips a dummy dim)."""
    return _write_tail(buf[..., None], s[..., None])[..., 0]


def init_gqa_cache(att: AttentionConfig, batch: int, max_len: int, window,
                   dtype, kv_quant=False):
    C = min(max_len, window) if window is not None else max_len
    shape = (batch, C, att.n_kv_heads, att.head_dim)
    if kv_quant:
        return {"k": jnp.zeros(shape, jnp.int8),
                "k_scale": jnp.zeros(shape[:3], jnp.float32),
                "v": jnp.zeros(shape, jnp.int8),
                "v_scale": jnp.zeros(shape[:3], jnp.float32)}
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


# ---------------------------------------------------------------------------
# MLA apply
# ---------------------------------------------------------------------------

def _mla_q(params, att: AttentionConfig, x, pos_q, eps):
    B, S, _ = x.shape
    qk_dim = att.qk_nope_head_dim + att.qk_rope_head_dim
    if att.q_lora_rank:
        cq = rms_norm(jnp.einsum("bsd,df->bsf", x, params["w_dq"]), params["q_norm"], eps)
        q = jnp.einsum("bsf,fg->bsg", cq, params["w_uq"])
    else:
        q = jnp.einsum("bsd,df->bsf", x, params["wq"])
    q = q.reshape(B, S, att.n_heads, qk_dim)
    q = shard(q, None, None, "model", None)
    q_nope = q[..., : att.qk_nope_head_dim]
    q_rope = apply_rope(q[..., att.qk_nope_head_dim:], pos_q, att.rope_theta)
    return q_nope, q_rope


def apply_mla(params, att: AttentionConfig, x, pos_q, *, window, eps,
              cache=None, cache_pos=None):
    """MLA attention. cache: {"ckv": (B,C,r), "k_rope": (B,C,rd)} or None."""
    B, S, d = x.shape
    H = att.n_heads
    nope, rd, vd, r = att.qk_nope_head_dim, att.qk_rope_head_dim, att.v_head_dim, att.kv_lora_rank
    scale = 1.0 / math.sqrt(nope + rd)

    q_nope, q_rope = _mla_q(params, att, x, pos_q, eps)

    dkv = jnp.einsum("bsd,df->bsf", x, params["w_dkv"])
    ckv = rms_norm(dkv[..., :r], params["kv_norm"], eps)          # (B,S,r)
    k_rope = apply_rope(dkv[..., r:][:, :, None, :], pos_q, att.rope_theta)[:, :, 0, :]

    if cache is None or S > 1:
        k_nope = jnp.einsum("bsr,rf->bsf", ckv, params["w_uk"]).reshape(B, S, H, nope)
        v = jnp.einsum("bsr,rf->bsf", ckv, params["w_uv"]).reshape(B, S, H, vd)
        k_nope = shard(k_nope, None, None, "model", None)
        v = shard(v, None, None, "model", None)
        q = jnp.concatenate([q_nope, q_rope], axis=-1)
        k = jnp.concatenate([k_nope, jnp.broadcast_to(k_rope[:, :, None, :], (B, S, H, rd))], axis=-1)
        xla = lambda q, k, v: attend(q, k, v, _mask_bias(pos_q, pos_q, window),
                                     att.logit_softcap, scale)
        if (cache is None and window is None and att.logit_softcap is None
                and block_size(S) is not None):
            # training / scoring: the fused kernel on TPU, xla elsewhere
            out = causal_attention(q, k, v, xla, scale=scale)
        else:
            out = xla(q, k, v)
        new_cache = None
        if cache is not None:  # prefill: store latent tail
            new_cache = {"ckv": _write_tail(cache["ckv"], ckv),
                         "k_rope": _write_tail(cache["k_rope"], k_rope)}
    else:
        # absorbed decode: scores & values in latent space, cache stays (r+rd).
        C = cache["ckv"].shape[1]
        slot = jnp.mod(cache_pos, C)
        ckv_c = jax.lax.dynamic_update_slice(cache["ckv"], ckv, (0, slot, 0))
        kr_c = jax.lax.dynamic_update_slice(cache["k_rope"], k_rope, (0, slot, 0))
        new_cache = {"ckv": ckv_c, "k_rope": kr_c}
        pos_k = _cache_positions(C, cache_pos)
        w_uk = params["w_uk"].reshape(r, H, nope)
        q_lat = jnp.einsum("bshn,rhn->bshr", q_nope.astype(jnp.float32), w_uk.astype(jnp.float32))
        scores = jnp.einsum("bshr,bcr->bhsc", q_lat, ckv_c.astype(jnp.float32))
        scores += jnp.einsum("bshr,bcr->bhsc", q_rope.astype(jnp.float32), kr_c.astype(jnp.float32))
        scores *= scale
        scores = softcap(scores, att.logit_softcap)
        bias = _mask_bias(pos_q, pos_k, window)
        w = jax.nn.softmax(scores + bias[None, None], axis=-1)
        o_lat = jnp.einsum("bhsc,bcr->bshr", w, ckv_c.astype(jnp.float32))
        w_uv = params["w_uv"].reshape(r, H, vd)
        out = jnp.einsum("bshr,rhv->bshv", o_lat, w_uv.astype(jnp.float32)).astype(x.dtype)

    out = jnp.einsum("bsf,fd->bsd", out.reshape(B, S, -1), params["wo"])
    return out, new_cache


def init_mla_cache(att: AttentionConfig, batch: int, max_len: int, window, dtype):
    C = min(max_len, window) if window is not None else max_len
    return {
        "ckv": jnp.zeros((batch, C, att.kv_lora_rank), dtype),
        "k_rope": jnp.zeros((batch, C, att.qk_rope_head_dim), dtype),
    }


# ---------------------------------------------------------------------------
# Unified entry
# ---------------------------------------------------------------------------

def apply_attention(params, cfg: ArchConfig, x, pos_q, *, is_local: bool,
                    cache=None, cache_pos=None):
    att = cfg.attention
    window = att.window if is_local else None
    if att.kind == "mla":
        # MLA's latent cache is already ~8x smaller than GQA KV; int8 applies
        # to the latent the same way (not enabled by default).
        return apply_mla(params, att, x, pos_q, window=window, eps=cfg.norm_eps,
                         cache=cache, cache_pos=cache_pos)
    return apply_gqa(params, att, x, pos_q, window=window, eps=cfg.norm_eps,
                     cache=cache, cache_pos=cache_pos, kv_quant=cfg.kv_quant)


def init_attention_cache(cfg: ArchConfig, is_local: bool, batch: int, max_len: int, dtype):
    att = cfg.attention
    window = att.window if is_local else None
    if att.kind == "mla":
        return init_mla_cache(att, batch, max_len, window, dtype)
    return init_gqa_cache(att, batch, max_len, window, dtype,
                          kv_quant=cfg.kv_quant)
