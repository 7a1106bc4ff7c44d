"""MLA's full-sequence causal attention: the splash kernel against ``attend``.

The kernel runs in the Pallas interpreter here; on the CPU the program
itself lowers the ``attend`` path, bit for bit as before the kernel was
wired in. Compiles for a described TPU are in ``test_tpu_compile.py``.
"""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_arch
from repro.core import local_sgd as LS
from repro.kernels.flash_attention.causal import block_size, splash_causal
from repro.models import attention as A
from repro.models import transformer as TF
from repro.obs import metrics as obs_metrics

# (B, S, H, qk head dim, v head dim): MLA's 96 / 64 heads; S 384 tiles
# into 3 x 3 blocks of 128, so blocks above the diagonal are skipped
SHAPES = [(2, 256, 4, 96, 64), (1, 384, 2, 96, 64)]
# float32: the kernel and attend agree to rounding. bfloat16: both round
# their output and gradients to bfloat16; every |value| here is below 8,
# where one bfloat16 step is at most 2**-5
TOL = {jnp.float32: 2e-5, jnp.bfloat16: 2.0 ** -5}


def _lowered(path):
    reg = obs_metrics.registry()
    if "attention.lowered" not in reg:
        return 0.0
    return reg["attention.lowered"].value(path=path)


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_splash_kernel_matches_attend(shape, dtype):
    B, S, H, dqk, dv = shape
    ks = jax.random.split(jax.random.key(0), 4)
    q = jax.random.normal(ks[0], (B, S, H, dqk)).astype(dtype)
    k = jax.random.normal(ks[1], (B, S, H, dqk)).astype(dtype)
    v = jax.random.normal(ks[2], (B, S, H, dv)).astype(dtype)
    do = jax.random.normal(ks[3], (B, S, H, dv)).astype(dtype)
    scale = 1.0 / math.sqrt(dqk)
    pos = jnp.arange(S)
    bias = A._mask_bias(pos, pos, None)

    out, vjp = jax.vjp(
        lambda q, k, v: splash_causal(q, k, v, scale=scale, interpret=True),
        q, k, v)
    ref, vjp_ref = jax.vjp(
        lambda q, k, v: A.attend(q, k, v, bias, None, scale), q, k, v)
    assert out.dtype == ref.dtype == dtype
    f32 = lambda x: np.asarray(x, np.float32)
    np.testing.assert_allclose(f32(out), f32(ref), atol=TOL[dtype], rtol=0)
    for name, g, g_ref in zip("qkv", vjp(do), vjp_ref(do)):
        np.testing.assert_allclose(f32(g), f32(g_ref), atol=TOL[dtype],
                                   rtol=0, err_msg=f"d{name}")


def test_block_size_from_sequence_length():
    assert [block_size(s) for s in (2048, 384, 256, 128, 4096)] == \
        [512, 128, 256, 128, 512]
    assert block_size(100) is None and block_size(64) is None


def test_cpu_lowers_xla_path_bit_identical(monkeypatch):
    """On the CPU the training loss and its gradients are bit for bit
    those of ``attend`` with the causal bias, which ``apply_mla`` called
    before the kernel was wired in."""
    cfg = get_arch("minicpm3-4b", smoke=True)
    params = TF.init_params(jax.random.key(0), cfg)
    toks = jax.random.randint(jax.random.key(1), (2, 128), 0, cfg.vocab_size)
    batch = {"tokens": toks, "labels": jnp.roll(toks, -1, axis=1)}

    def loss_and_grad():
        return jax.jit(jax.value_and_grad(
            lambda p: LS.lm_loss(p, cfg, batch)))(params)

    xla0, kernel0 = _lowered("xla"), _lowered("kernel")
    new = loss_and_grad()
    assert _lowered("xla") > xla0 and _lowered("kernel") == kernel0

    monkeypatch.setattr(A, "causal_attention",
                        lambda q, k, v, xla, *, scale: xla(q, k, v))
    old = loss_and_grad()
    leaves, old_leaves = jax.tree.leaves(new), jax.tree.leaves(old)
    assert len(leaves) == len(old_leaves)
    for a, b in zip(leaves, old_leaves):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def _attention_jaxpr(cfg, S, *, cache):
    params = TF.init_params(jax.random.key(0), cfg)
    toks = jnp.zeros((1, S), jnp.int32)
    if cache:
        c = TF.init_cache(cfg, 1, S + 8)
        return str(jax.make_jaxpr(
            lambda p, c: TF.prefill(p, cfg, toks, c))(params, c))
    return str(jax.make_jaxpr(lambda p: TF.forward(p, cfg, toks))(params))


@pytest.mark.parametrize("arch,S,cache,engages", [
    ("minicpm3-4b", 128, False, True),    # training / scoring
    ("minicpm3-4b", 96, False, False),    # no block divides S
    ("minicpm3-4b", 128, True, False),    # prefill into a cache
    ("qwen3-14b", 128, False, False),     # GQA keeps attend
])
def test_kernel_path_staged_only_where_it_applies(arch, S, cache, engages):
    jaxpr = _attention_jaxpr(get_arch(arch, smoke=True), S, cache=cache)
    assert ("attention_lowered" in jaxpr) == engages
    assert ("platform_index" in jaxpr) == engages
