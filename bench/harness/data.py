"""Weights and batches, made by the benchmark from ``--seed``.

The program is handed both; it makes neither. The weights follow the
program's parameter layout (taken from its shapes) and the scales the
program's own initialiser uses, so a run trains from the same kind of
start; they are drawn on the device in one jitted call.
"""
from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp
import numpy as np


def seed_words(seed: int) -> np.ndarray:
    """Two 32-bit words from a seed of any size (``--seed`` may exceed
    what a signed 32-bit integer holds)."""
    return np.random.SeedSequence(int(seed)).generate_state(2)


def jax_key(seed: int):
    return jax.random.key(int(seed_words(seed)[0] & 0x7FFFFFFF))


def _leaf_init(path, shape, dtype, key):
    name = str(getattr(path[-1], "key", path[-1]))
    key = jax.random.fold_in(key, zlib.crc32(jax.tree_util.keystr(path).encode()))
    normal = lambda: jax.random.normal(key, shape, jnp.float32)  # noqa: E731
    if name in ("embed", "unembed"):
        x = normal() * 0.02
    elif name == "D":
        x = jnp.ones(shape, jnp.float32)
    elif name in ("A_log", "dt_bias") or name.startswith("ln") or name.endswith("norm"):
        x = jnp.zeros(shape, jnp.float32)
    elif name == "conv_w":
        x = normal() * 0.1
    else:                                    # (..., fan_in, fan_out) matrices
        x = normal() / np.sqrt(shape[-2])
    return x.astype(dtype)


def init_params(key, param_shapes):
    """One replica of the weights, in the program's layout and dtypes."""
    return jax.tree_util.tree_map_with_path(
        lambda p, s: _leaf_init(p, s.shape, s.dtype, key), param_shapes)


def host_batches(seed: int, traffic: dict, vocab: int, n: int):
    """``n`` distinct training batches, {"tokens", "labels"} of shape
    (clients, batch_per_client, seq_len), token ids uniform over the
    vocabulary; labels are the next tokens."""
    rng = np.random.default_rng(seed_words(seed))
    C, B, S = traffic["clients"], traffic["batch_per_client"], traffic["seq_len"]
    toks = rng.integers(0, vocab, size=(n, C, B, S + 1), dtype=np.int32)
    return [{"tokens": t[..., :-1], "labels": t[..., 1:]} for t in toks]
