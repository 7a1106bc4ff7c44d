"""Compiles for one described TPU v5e chip: what its compiler refuses.

Nothing runs. The TPU compiler is installed with jax and compiles for a
chip that is described, not attached, so these tests catch kernels the
chip would refuse (tiling, casts, fast-memory use) and programs that do
not fit its memory, at the real widths of the smoke's main path:
MiniCPM3-4B's largest layer leaf (2560 x 6400) and its 4-layer sync round.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and every test worker imports this
file.
"""
import math
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.configs import get_arch
from repro.core import local_sgd as LS
from repro.kernels.fused_update.ops import sgd_update
from repro.kernels.quantize import ops as Q
from repro.obs import metrics as obs_metrics
from repro.sharding import scatter_dim

LEAF = 2560 * 6400           # MiniCPM3-4B's w_gate / w_up / w_down
CLIENTS = 2
HBM_BYTES = 15.75e9          # one v5e chip's HBM as its compiler sees it


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler to describe the chip with
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip can be written to the persistent
    # cache but never read back without one: keep it out of the cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


def test_quantize_kernels_compile_for_v5e(one_chip):
    y = _spec((CLIENTS, LEAF), jnp.float32, one_chip)
    rbits = _spec((CLIENTS, LEAF), jnp.uint32, one_chip)
    scales = _spec((CLIENTS,), jnp.float32, one_chip)
    enc = _compile(lambda a, b, c: Q.encode_leaf(a, b, c, impl="pallas"),
                   y, rbits, scales)
    q = _spec((CLIENTS, LEAF), jnp.int8, one_chip)
    dec = _compile(lambda a, c: Q.dequant_mean(a, c, impl="pallas"),
                   q, scales)
    for compiled in (enc, dec):
        assert "tpu_custom_call" in compiled.as_text()


def test_fused_sgd_update_compiles_for_v5e(one_chip):
    p = _spec((2560, 6400), jnp.bfloat16, one_chip)
    m = _spec((2560, 6400), jnp.float32, one_chip)
    compiled = _compile(
        lambda p, m, g: sgd_update(p, m, g, eta=0.01, beta=0.9,
                                   impl="pallas"), p, m, p)
    assert "tpu_custom_call" in compiled.as_text()


def test_minicpm3_sync_step_fits_one_v5e(one_chip):
    cfg = get_arch("minicpm3-4b", layers=4)
    state = jax.tree.map(lambda x: _spec(x.shape, x.dtype, one_chip),
                         LS.init_state_shape(cfg, CLIENTS))
    compiled = jax.jit(LS.build_sync_step(), donate_argnums=(0,)).lower(
        state).compile()
    m = compiled.memory_analysis()
    need = (m.argument_size_in_bytes + m.output_size_in_bytes
            - m.alias_size_in_bytes + m.temp_size_in_bytes)
    assert m.alias_size_in_bytes >= 0.99 * m.argument_size_in_bytes  # donated
    assert need <= HBM_BYTES, need


# the local step with attention through `attend`'s f32 2048 x 2048
# scores, compiled for the same chips: temp bytes per chip
SCORES_LOCAL_STEP_TEMP = {1: 7_203_408_896, 4: 2_955_895_296}
# what the fused kernel must save at least: 1.3 GB with two clients on a
# chip, 1.0 GB with one (the score tensors it no longer writes)
KERNEL_SAVES = {1: 1.3e9, 4: 1.0e9}


def _bench_steps(topo, cfg, chips, clients):
    """The benchmark's mesh, its two steps and the state's shapes in its
    shardings, on ``chips`` described v5e chips."""
    mesh = jax.make_mesh((chips, 1), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2,
                         devices=topo.devices[:chips])
    local, sync, _ = LS.build_train_steps(cfg, mesh, client_axis="data",
                                          momentum=0.9)
    shapes = LS.init_state_shape(cfg, clients)
    shardings = LS.state_shardings(cfg, mesh, shapes["params"],
                                   shapes["opt"])
    state = jax.tree.map(lambda sh, x: _spec(x.shape, x.dtype, sh),
                         shardings, shapes)
    return mesh, local, sync, state


def _compile_local_step(topo, cfg, chips, clients):
    """The benchmark's local step (1 x 2048 tokens a client), its state
    donated, compiled for ``chips`` described v5e chips."""
    mesh, local, _, state = _bench_steps(topo, cfg, chips, clients)
    specs = LS.batch_spec(cfg, "data", False)
    batch = {k: _spec((clients, 1, 2048), jnp.int32,
                      NamedSharding(mesh, specs[k]))
             for k in ("tokens", "labels")}
    eta = _spec((), jnp.float32, NamedSharding(mesh, P()))
    return jax.jit(local, donate_argnums=(0,)).lower(
        state, batch, eta).compile()


@pytest.mark.parametrize("chips,clients", [(1, 2), (4, 4)])
def test_minicpm3_local_step_attends_through_splash_kernel(topo, chips,
                                                          clients):
    """The benchmark's local step (MiniCPM3-l4, 1 x 2048 tokens a
    client) lowers MLA's attention to the splash kernel: no 2048 x 2048
    buffer is left, the temp bytes fall, and on four chips the kernel's
    shard_map adds no collective (the one all-reduce is the scalar mean
    loss, which the step had before the kernel too)."""
    cfg = get_arch("minicpm3-4b", layers=4)
    reg = obs_metrics.registry()
    before = (reg["attention.lowered"].value(path="kernel")
              if "attention.lowered" in reg else 0.0)
    compiled = _compile_local_step(topo, cfg, chips, clients)
    assert reg["attention.lowered"].value(path="kernel") > before

    text = compiled.as_text()
    assert 'custom_call_target="tpu_custom_call"' in text
    assert "splash_mha_fwd" in text and "splash_mha_dkv" in text
    assert not re.search(r",2048,2048\]", text)
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp <= SCORES_LOCAL_STEP_TEMP[chips] - KERNEL_SAVES[chips], temp
    assert "all-gather" not in text
    reduces = re.findall(r"= (\S+) all-reduce\(", text)
    assert reduces == ([] if chips == 1 else ["f32[]{:T(128)}"]), reduces


# the Mamba2-l8 local step's temp bytes on one chip with two clients,
# compiled with jax 0.9.0 and its libtpu: with the 5.41 GB donated state,
# 10.85 GB of the chip's 15.75 GB (as when the cell was first compiled,
# before the SSD's segment sums were masked before exp)
MAMBA2_LOCAL_STEP_TEMP = 5_439_823_872


def test_mamba2_local_step_fits_one_v5e(topo):
    """The benchmark's Mamba2-2.7B local step (8 layers at published
    widths, 2 clients x 1 x 2048 tokens on one chip) fits the chip's
    memory with its state donated, and its temp bytes stay within 5% of
    what they were when the SSD's backward was made finite: the masked
    segment sums add no buffer."""
    cfg = get_arch("mamba2-2.7b", layers=8)
    reg = obs_metrics.registry()
    before = (reg["ssd.lowered"].value(chunk=256)
              if "ssd.lowered" in reg else 0.0)
    compiled = _compile_local_step(topo, cfg, 1, CLIENTS)
    assert reg["ssd.lowered"].value(chunk=256) > before
    m = compiled.memory_analysis()
    need = (m.argument_size_in_bytes + m.output_size_in_bytes
            - m.alias_size_in_bytes + m.temp_size_in_bytes)
    assert m.alias_size_in_bytes >= 0.99 * m.argument_size_in_bytes  # donated
    assert need <= HBM_BYTES, need
    assert abs(m.temp_size_in_bytes / MAMBA2_LOCAL_STEP_TEMP - 1) <= 0.05, \
        m.temp_size_in_bytes


# the four-chip round as nine float32 all-reduces (before it was averaged
# by reduce-scatter and all-gather): bytes a chip over the client axis by
# `hlo_analysis`'s ring model, compiled with jax 0.9.0 and its libtpu
ALL_REDUCE_ROUND_LINK_BYTES = 5_265_487_872


def _lowered(path):
    reg = obs_metrics.registry()
    return (reg["sync.lowered"].value(path=path)
            if "sync.lowered" in reg else 0.0)


def _fits(compiled):
    m = compiled.memory_analysis()
    need = (m.argument_size_in_bytes + m.output_size_in_bytes
            - m.alias_size_in_bytes + m.temp_size_in_bytes)
    assert m.alias_size_in_bytes >= 0.99 * m.argument_size_in_bytes  # donated
    assert need <= HBM_BYTES, need


def _typed(text, op):
    """The result types of every ``op`` in an HLO text, as ``dtype[dims]``."""
    return [f"{t}[{d}]" for t, d in
            re.findall(rf"= (\w+)\[([\d,]*)\]\S* {op}\(", text)]


def test_minicpm3_four_chip_round_gathers_in_leaf_dtype(topo):
    """The benchmark's four-chip MiniCPM3-l4 round (one client a chip, the
    state donated) averages its bfloat16 params by float32
    reduce-scatters and bfloat16 all-gathers: no bfloat16 leaf but the
    small norm scales is widened into an all-reduce (the float32 momentum
    keeps it), every bfloat16 leaf that has a dimension to scatter is
    gathered in bfloat16, the client axis carries at most 0.9 of the
    all-reduce round's bytes, no copy of the state is added, and it fits
    the chip."""
    from repro.launch import hlo_analysis as H

    cfg = get_arch("minicpm3-4b", layers=4)
    before = _lowered("scatter_gather")
    _, _, sync, state = _bench_steps(topo, cfg, 4, 4)
    compiled = jax.jit(sync, donate_argnums=(0,)).lower(state).compile()
    assert _lowered("scatter_gather") > before
    _fits(compiled)

    text = compiled.as_text()
    # what is left on the all-reduce is the momentum and the norm scales
    momentum = sum(x.size // x.shape[0] * x.dtype.itemsize
                   for x in jax.tree.leaves(state["opt"]))  # one replica
    reduced = sum(c["bytes"] for c in H.parse_collectives(
        text, {"data": 4, "model": 1}) if c["kind"] == "all-reduce")
    assert momentum <= reduced < momentum + 1e6, (reduced, momentum)
    scattered = [x.shape[1:] for x in jax.tree.leaves(state["params"])
                 if x.dtype == jnp.bfloat16
                 and scatter_dim(x.shape[1:], 4) is not None]
    assert len(scattered) == 10
    gathered = _typed(text, "all-gather")
    for shape in scattered:
        assert f"bf16[{','.join(map(str, shape))}]" in gathered, shape
    assert not _typed(text, "copy")
    assert "tpu_custom_call" not in text   # the replicas share no device
    link = H.collective_summary(H.parse_collectives_nested(
        text, {"data": 4, "model": 1}))["by_axes"]["data"]
    assert link <= 0.9 * ALL_REDUCE_ROUND_LINK_BYTES, link


# the one-chip round's temp bytes as jnp.mean + broadcast_to, before the
# replicas were averaged in place, compiled with jax 0.9.0 and its libtpu
MEAN_ROUND_TEMP = {"minicpm3-4b": 1_129_433_088, "mamba2-2.7b": 1_299_578_880}
IN_PLACE_TEMP = 64e6         # the in-place round's temp bytes at most
RELAYOUT_BYTES = 100e6       # no copy, transpose or fusion this large
DTYPE_BYTES = {"pred": 1, "s8": 1, "u8": 1, "bf16": 2, "f16": 2, "s32": 4,
               "u32": 4, "f32": 4}


def _entry_outputs(text, ops):
    """Bytes of the result of each ``ops`` instruction of an HLO text's
    entry computation."""
    entry = text[text.index("\nENTRY"):]
    entry = entry[:entry.index("\n}")]
    return [sum(DTYPE_BYTES[t] * math.prod(map(int, filter(None, d.split(","))))
                for t, d in re.findall(r"(\w+)\[([\d,]*)\]", result))
            for result, op in re.findall(r"= (.+?) ([a-z][\w\-]*)\(", entry)
            if op in ops]


def _one_chip_round_averages_in_place(topo, name, layers):
    """The benchmark's one-chip round of ``name`` (two clients sharing the
    chip, the state donated): it counts ``colocated``, averages its leaves
    through the kernel (``tpu_custom_call``) with no collective, keeps at
    most ``IN_PLACE_TEMP`` of temp bytes against ``MEAN_ROUND_TEMP``, and
    its entry computation relays out no leaf: no copy, transpose or fusion
    of ``RELAYOUT_BYTES`` or more."""
    cfg = get_arch(name, layers=layers)
    before = _lowered("colocated")
    _, _, sync, state = _bench_steps(topo, cfg, 1, CLIENTS)
    compiled = jax.jit(sync, donate_argnums=(0,)).lower(state).compile()
    assert _lowered("colocated") > before
    _fits(compiled)

    text = compiled.as_text()
    assert 'custom_call_target="tpu_custom_call"' in text
    assert not re.search(
        r" (all-reduce|all-gather|reduce-scatter)(-start)?\(", text)
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp <= IN_PLACE_TEMP < MEAN_ROUND_TEMP[name], temp
    big = [b for b in _entry_outputs(text, ("copy", "transpose", "fusion"))
           if b >= RELAYOUT_BYTES]
    assert not big, big


def test_minicpm3_one_chip_round_keeps_the_all_reduce_mean(topo):
    """MiniCPM3-l4's one-chip round keeps the all-reduce round's mean (the
    float32 sum of the two replicas over two, rounded once; bit for bit
    on the CPU in tests/test_replica_mean.py) but computes it in place:
    see ``_one_chip_round_averages_in_place``. Its stacked w_dkv
    (2560 x 288) is laid out with 2560 minor and taken in that view."""
    _one_chip_round_averages_in_place(topo, "minicpm3-4b", 4)


def test_mamba2_one_chip_round_averages_in_place(topo):
    """Mamba2-l8's one-chip round, whose stacked w_in (2560 x 10576, half
    the state's bytes with its momentum) is laid out with 2560 minor:
    see ``_one_chip_round_averages_in_place``."""
    _one_chip_round_averages_in_place(topo, "mamba2-2.7b", 8)
