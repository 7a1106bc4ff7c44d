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
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_arch
from repro.core import local_sgd as LS
from repro.kernels.fused_update.ops import sgd_update
from repro.kernels.quantize import ops as Q

LEAF = 2560 * 6400           # MiniCPM3-4B's w_gate / w_up / w_down
CLIENTS = 2
HBM_BYTES = 15.75e9          # one v5e chip's HBM as its compiler sees it


@pytest.fixture(scope="module")
def one_chip():
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
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


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
