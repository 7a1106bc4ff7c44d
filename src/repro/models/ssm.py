"""Mamba2 block — SSD (state-space duality), chunked matmul form. [arXiv:2405.21060]

TPU adaptation (see DESIGN.md §2/§6): the SSD algorithm is evaluated in its
*dual* chunked-matmul form — intra-chunk terms are attention-like (Q,Q) and
(N,P) matmuls that map directly onto the MXU, and the inter-chunk recurrence
is a short ``lax.scan`` over S/chunk states. This replaces the paper's
warp-level CUDA scan with a layout the TPU memory hierarchy actually likes.

Full-sequence path: ``apply_mamba2(...)``. Decode path keeps O(1) state:
conv ring (d_conv-1 inputs) + SSM state (H, P, N).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig, SSMConfig
from repro.models.layers import dense_init, rms_norm
from repro.obs.lowered import lowering_counter
from repro.sharding import shard

_lowered = lowering_counter("ssd.lowered", help="chunked SSDs lowered, by chunk")


def _dims(cfg: ArchConfig):
    ssm = cfg.ssm
    d_inner = ssm.expand * cfg.d_model
    n_heads = d_inner // ssm.head_dim
    conv_ch = d_inner + 2 * ssm.n_groups * ssm.d_state
    d_in_proj = 2 * d_inner + 2 * ssm.n_groups * ssm.d_state + n_heads
    return d_inner, n_heads, conv_ch, d_in_proj


def init_mamba2(key, cfg: ArchConfig, dtype):
    ssm = cfg.ssm
    d_inner, n_heads, conv_ch, d_in_proj = _dims(cfg)
    keys = jax.random.split(key, 4)
    return {
        "w_in": dense_init(keys[0], cfg.d_model, d_in_proj, dtype),
        "conv_w": (jax.random.normal(keys[1], (ssm.d_conv, conv_ch), jnp.float32) * 0.1).astype(dtype),
        "A_log": jnp.zeros((n_heads,), jnp.float32),
        "D": jnp.ones((n_heads,), jnp.float32),
        "dt_bias": jnp.zeros((n_heads,), jnp.float32),
        "ssm_norm": jnp.zeros((d_inner,), dtype),
        "w_out_ssm": dense_init(keys[2], d_inner, cfg.d_model, dtype),
    }


def _split_in_proj(cfg: ArchConfig, zxbcdt):
    ssm = cfg.ssm
    d_inner, n_heads, _, _ = _dims(cfg)
    gN = ssm.n_groups * ssm.d_state
    z, xs, Bc, Cc, dt = jnp.split(
        zxbcdt, [d_inner, 2 * d_inner, 2 * d_inner + gN, 2 * d_inner + 2 * gN], axis=-1
    )
    return z, xs, Bc, Cc, dt


def _causal_conv(x, w, carry=None):
    """Depthwise causal conv. x: (B,S,ch), w: (K,ch). carry: (B,K-1,ch) or None."""
    K = w.shape[0]
    if carry is None:
        pad = jnp.zeros((x.shape[0], K - 1, x.shape[2]), x.dtype)
    else:
        pad = carry.astype(x.dtype)
    xp = jnp.concatenate([pad, x], axis=1)  # (B, S+K-1, ch)
    out = sum(xp[:, i : i + x.shape[1], :] * w[i][None, None, :] for i in range(K))
    new_carry = xp[:, -(K - 1):, :]
    return jax.nn.silu(out), new_carry


def _segsum_exp(dA):
    """dA: (..., Q). Return exp(segsum) lower-tri matrix (..., Q, Q):
    L[i,j] = exp(sum_{j<k<=i} dA_k) for i>=j else 0.

    Masked before the exponential: above the diagonal the segment sums
    are positive (dA <= 0) and over a chunk of 256 pass float32's exp
    range, and an ``inf`` there times the zero cotangent the mask gives
    is NaN in the backward pass."""
    cs = jnp.cumsum(dA, axis=-1)
    diff = cs[..., :, None] - cs[..., None, :]
    Q = dA.shape[-1]
    tri = jnp.tril(jnp.ones((Q, Q), bool))
    return jnp.exp(jnp.where(tri, diff, -jnp.inf))


def ssd_chunked(xs, dt, A, Bc, Cc, chunk: int, initial_state=None):
    """SSD in chunked dual form.

    xs: (B,S,H,P)  dt: (B,S,H)  A: (H,)  Bc,Cc: (B,S,G,N)
    Returns y (B,S,H,P), final_state (B,H,P,N). All math fp32.

    The work sits under ``jax.named_scope("ssd")`` with one inner scope a
    phase (``intra_chunk``, ``chunk_states``, ``inter_chunk_scan``,
    ``read_out``), and each SSD lowered counts once under
    ``ssd.lowered{chunk}``. Every exponent but the masked upper triangle
    of ``_segsum_exp`` is a sum of dt·A <= 0 (A < 0, dt > 0), so no
    ``exp`` here can overflow.
    """
    Bsz, S, H, P = xs.shape
    G, N = Bc.shape[2], Bc.shape[3]
    assert S % chunk == 0, (S, chunk)
    if G > 1:
        raise NotImplementedError("n_groups > 1 not needed by assigned archs")
    nc = S // chunk
    rep = H // G

    with jax.named_scope("ssd"):
        xs = xs.astype(jnp.float32).reshape(Bsz, nc, chunk, H, P)
        dt = dt.astype(jnp.float32).reshape(Bsz, nc, chunk, H)
        Bc = Bc.astype(jnp.float32).reshape(Bsz, nc, chunk, G, N)
        Cc = Cc.astype(jnp.float32).reshape(Bsz, nc, chunk, G, N)

        dA = dt * A[None, None, None, :]          # (B,nc,Q,H)
        dAh = jnp.moveaxis(dA, -1, 2)             # (B,nc,H,Q)
        xdt = xs * dt[..., None]                  # dt-weighted inputs

        # intra-chunk (diagonal) term: "attention" C_i · B_j with decay L
        with jax.named_scope("intra_chunk"):
            L = _segsum_exp(dAh)                               # (B,nc,H,Q,Q)
            CB = jnp.einsum("bnqgi,bnsgi->bngqs", Cc, Bc)      # (B,nc,G,Q,Q)
            CB = jnp.repeat(CB, rep, axis=2)                   # (B,nc,H,Q,Q)
            y_diag = jnp.einsum("bnhqs,bnshp->bnqhp", CB * L, xdt)

        # per-chunk final states: sum_j decay_to_end_j * B_j x_j
        with jax.named_scope("chunk_states"):
            seg_end = jnp.exp(jnp.cumsum(dAh, axis=-1)[..., -1:] - jnp.cumsum(dAh, axis=-1))  # (B,nc,H,Q)
            states = jnp.einsum(
                "bnshp,bnsgi,bnhs->bnhpi", xdt, Bc, seg_end
            )  # (B,nc,H,P,N) for G=1

        # inter-chunk recurrence over chunk states
        with jax.named_scope("inter_chunk_scan"):
            chunk_decay = jnp.exp(jnp.sum(dAh, axis=-1))  # (B,nc,H)

            def step(carry, inp):
                st, dec = inp
                new = carry * dec[..., None, None] + st
                return new, carry  # emit state *entering* the chunk

            init = (
                jnp.zeros((Bsz, H, P, N), jnp.float32)
                if initial_state is None
                else initial_state.astype(jnp.float32)
            )
            final_state, prev_states = jax.lax.scan(
                step,
                init,
                (jnp.moveaxis(states, 1, 0), jnp.moveaxis(chunk_decay, 1, 0)),
            )
            prev_states = jnp.moveaxis(prev_states, 0, 1)  # (B,nc,H,P,N)

        # off-diagonal contribution: C_i · (decay_from_start_i * state_in)
        with jax.named_scope("read_out"):
            seg_start = jnp.exp(jnp.cumsum(dAh, axis=-1))  # decay from chunk start to i (inclusive)
            y_off = jnp.einsum("bnqgi,bnhpi,bnhq->bnqhp", Cc, prev_states, seg_start)
            y = (y_diag + y_off).reshape(Bsz, S, H, P)
    return _lowered(y, chunk=chunk), final_state


def apply_mamba2(params, cfg: ArchConfig, x, cache=None):
    """x: (B,S,d). cache: None or {"conv": (B,K-1,ch), "state": (B,H,P,N)}."""
    ssm = cfg.ssm
    d_inner, n_heads, conv_ch, _ = _dims(cfg)
    B_, S, _ = x.shape
    zxbcdt = jnp.einsum("bsd,df->bsf", x, params["w_in"])
    zxbcdt = shard(zxbcdt, None, None, "model")
    z, xs, Bc, Cc, dt = _split_in_proj(cfg, zxbcdt)

    conv_in = jnp.concatenate([xs, Bc, Cc], axis=-1)
    conv_carry = None if cache is None else cache["conv"]
    conv_out, new_conv = _causal_conv(conv_in, params["conv_w"], conv_carry)
    xs = conv_out[..., :d_inner]
    Bc = conv_out[..., d_inner : d_inner + ssm.n_groups * ssm.d_state]
    Cc = conv_out[..., d_inner + ssm.n_groups * ssm.d_state :]

    xs = xs.reshape(B_, S, n_heads, ssm.head_dim)
    Bc = Bc.reshape(B_, S, ssm.n_groups, ssm.d_state)
    Cc = Cc.reshape(B_, S, ssm.n_groups, ssm.d_state)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + params["dt_bias"])
    A = -jnp.exp(params["A_log"])

    if cache is None or S > 1:
        init_state = None if cache is None else cache["state"]
        chunk = min(ssm.chunk_size, S)
        y, final_state = ssd_chunked(xs, dt, A, Bc, Cc, chunk, init_state)
    else:
        # single-token recurrent decode: state' = exp(dt·A)·state + dt·x Bᵀ
        st = cache["state"].astype(jnp.float32)  # (B,H,P,N)
        dA1 = jnp.exp(dt[:, 0] * A[None, :])  # (B,H)
        xb = jnp.einsum(
            "bhp,bgn->bhpn", (xs[:, 0] * dt[:, 0, :, None]).astype(jnp.float32),
            Bc[:, 0].astype(jnp.float32),
        )
        final_state = st * dA1[..., None, None] + xb
        y = jnp.einsum("bhpn,bgn->bhp", final_state, Cc[:, 0].astype(jnp.float32))[:, None]

    y = y + xs.astype(jnp.float32) * params["D"][None, None, :, None]
    y = y.reshape(B_, S, d_inner).astype(x.dtype)
    y = rms_norm(y * jax.nn.silu(z.astype(jnp.float32)).astype(x.dtype), params["ssm_norm"], cfg.norm_eps)
    out = jnp.einsum("bsf,fd->bsd", y, params["w_out_ssm"])
    new_cache = None
    if cache is not None:
        new_cache = {"conv": new_conv, "state": final_state.astype(cache["state"].dtype)}
    return out, new_cache


def init_mamba2_cache(cfg: ArchConfig, batch: int, dtype):
    ssm = cfg.ssm
    d_inner, n_heads, conv_ch, _ = _dims(cfg)
    return {
        "conv": jnp.zeros((batch, ssm.d_conv - 1, conv_ch), dtype),
        "state": jnp.zeros((batch, n_heads, ssm.head_dim, ssm.d_state), jnp.float32),
    }
