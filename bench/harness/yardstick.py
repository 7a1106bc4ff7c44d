"""The benchmark's own arithmetic: model FLOPs and the round's least bytes.

Model FLOPs of one local step, over all clients:

    6 x (matmul parameters) x tokens + 3 x (mixer forward FLOPs)

Matmul parameters are each layer block's weight matrices plus the output
projection over the published vocabulary (the embedding lookup is no
matmul, and the rows the program pads its vocabulary with are no model
work). The mixer's forward FLOPs are the ones its block file counts
(causal MLA score and value pairs, the chunked SSD); forward plus backward
is three times the forward. Recomputation under remat is never counted.
"""
from __future__ import annotations

import dataclasses

from bench.harness.spec import block_module


def arch_config(config: dict):
    """The program's ``ArchConfig`` for a configuration file."""
    from repro.configs.base import ArchConfig, AttentionConfig, SSMConfig

    fields = {f.name for f in dataclasses.fields(ArchConfig)}
    kw = {k: v for k, v in config.items() if k in fields}
    if kw.get("attention"):
        kw["attention"] = AttentionConfig(**kw["attention"])
    if kw.get("ssm"):
        kw["ssm"] = SSMConfig(**kw["ssm"])
    kw["block_pattern"] = tuple(kw["block_pattern"])
    return ArchConfig(**kw)


def layer_kinds(config: dict):
    pattern = config["block_pattern"]
    return [pattern[i % len(pattern)] for i in range(config["n_layers"])]


def blocks_of(config: dict, kind: str):
    return [block_module(b) for b in config["layer_blocks"][kind]]


def matmul_params(config: dict) -> int:
    n = config["vocab_size"] * config["d_model"]            # output projection
    for kind in layer_kinds(config):
        n += sum(b.matmul_params(config) for b in blocks_of(config, kind))
    return n


def mixer_flops(config: dict, seq_len: int) -> float:
    """Forward FLOPs of the sequence mixers of one sequence."""
    return sum(b.mixer_flops(config, seq_len)
               for kind in layer_kinds(config)
               for b in blocks_of(config, kind))


def step_flops(config: dict, traffic: dict) -> float:
    """Model FLOPs of one local step of every client."""
    seqs = traffic["clients"] * traffic["batch_per_client"]
    tokens = seqs * traffic["seq_len"]
    return (6.0 * matmul_params(config) * tokens
            + 3.0 * seqs * mixer_flops(config, traffic["seq_len"]))


def round_least_bytes(state_shapes, replicas_on_chip: int) -> int:
    """Least HBM bytes of one averaging round on a chip that holds
    ``replicas_on_chip`` replicas: read and write each replica's
    parameters and optimizer moments once. ``state_shapes`` is the
    training state (leaves with a leading client axis) as shapes."""
    import jax

    per_replica = 0
    for tree in (state_shapes["params"], state_shapes["opt"]):
        for leaf in jax.tree.leaves(tree):
            per_replica += (leaf.size // leaf.shape[0]) * leaf.dtype.itemsize
    return 2 * replicas_on_chip * per_replica
