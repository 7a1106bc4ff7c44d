"""The dense round's mean of bfloat16 leaves as a float32 reduce-scatter
and a bfloat16 all-gather (``local_sgd._gathered_mean``), on fake CPU
devices: bit for bit the all-reduce's ``tree_mean_leading`` +
``tree_broadcast_leading`` where each device holds one client replica,
and today's all-reduce wherever it does not engage; two clients on one
device count ``colocated`` and lower, on the CPU, to the same mean. The
round runs in a subprocess so that XLA's device-count flag does not leak into this one.
"""
import json
import os
import subprocess
import sys

import pytest

from repro.core import local_sgd as LS
from repro.sharding import scatter_dim

SRC = os.path.join(os.path.dirname(__file__), "..", "src")

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys; sys.path.insert(0, "@SRC@")
import json
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.core import local_sgd as LS
from repro.obs import metrics as obs_metrics
from repro.utils.tree import tree_broadcast_leading, tree_mean_leading

# per-replica shapes: a stacked layer leaf (scatters dim 0), an
# embedding-like leaf (scatters its columns), a norm stack (rows < 8) and
# leaves with no dimension to scatter (fall back to the all-reduce)
LEAVES = {"stack": (4, 16, 256), "embed": (40, 1024), "norm": (4, 256),
          "odd": (9, 10), "vec": (64,)}


def case(data, model, clients=None):
    clients = clients or data
    mesh = jax.make_mesh((data, model), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2,
                         devices=jax.devices()[:data * model])
    key = jax.random.key(7)
    leaf = lambda i, s, dt: (3 * jax.random.normal(
        jax.random.fold_in(key, i), (clients,) + s)).astype(dt)
    state = {"params": {k: leaf(i, s, jnp.bfloat16)
                        for i, (k, s) in enumerate(LEAVES.items())},
             "opt": {"mu": {k: leaf(10 + i, s, jnp.float32)
                            for i, (k, s) in enumerate(LEAVES.items())}},
             "step": jnp.zeros((), jnp.int32)}
    state = jax.device_put(state, jax.tree.map(
        lambda x: NamedSharding(mesh, P("data") if x.ndim else P()), state))
    reg = obs_metrics.registry()
    count = lambda path: (reg["sync.lowered"].value(path=path)
                          if "sync.lowered" in reg else 0.0)
    before = {p: count(p) for p in ("scatter_gather", "colocated",
                                    "all_reduce")}
    sync = jax.jit(LS.build_sync_step(mesh=mesh, client_axis="data"))
    out = sync(state)
    text = sync.lower(state).compile().as_text()
    ref = jax.jit(lambda s: dict(s, **{
        k: tree_broadcast_leading(tree_mean_leading(s[k]), clients)
        for k in ("params", "opt")}))(state)
    same = {}
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(out),
                            jax.tree.leaves(ref)):
        a, b = np.atleast_1d(a), np.atleast_1d(b)
        same[jax.tree_util.keystr(path)] = {
            "dtype": str(a.dtype), "equal": bool(
                a.dtype == b.dtype and np.array_equal(
                    a.view(np.uint8), b.view(np.uint8)))}
    return {"leaves": same,
            "lowered": {p: count(p) - before[p] for p in before},
            "reduce_scatters": text.count(" reduce-scatter("),
            "sharded": all(x.sharding.spec[:1] == P("data")
                           for x in jax.tree.leaves(
                               {k: out[k] for k in ("params", "opt")}))}


print(json.dumps({"4x1": case(4, 1), "2x4": case(2, 4),
                  "1x1": case(1, 1, clients=2)}))
"""


@pytest.fixture(scope="module")
def rounds():
    script = SCRIPT.replace("@SRC@", os.path.abspath(SRC))
    out = subprocess.run([sys.executable, "-c", script],
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


LEAF_PATHS = [f"['{part}']{mu}['{name}']"
              for part, mu in (("params", ""), ("opt", "['mu']"))
              for name in ("stack", "embed", "norm", "odd", "vec")]


@pytest.mark.parametrize("mesh", ["4x1", "2x4", "1x1"])
@pytest.mark.parametrize("leaf", LEAF_PATHS)
def test_round_equals_all_reduce_mean_bit_for_bit(rounds, mesh, leaf):
    """Every leaf of the round, bfloat16 params and float32 momentum,
    scattered or fallen back, equals ``tree_mean_leading`` +
    ``tree_broadcast_leading`` bit for bit."""
    got = rounds[mesh]["leaves"][leaf]
    assert got["dtype"] == ("bfloat16" if leaf.startswith("['params']")
                            else "float32")
    assert got["equal"], (mesh, leaf)


def test_one_replica_per_device_scatters_and_gathers(rounds):
    """On a 4-device ``data`` mesh the round counts ``scatter_gather`` and
    reduce-scatters the two bfloat16 leaves that have a dimension to
    scatter (the float32 momentum keeps the all-reduce); the consensus
    stays split over ``data``."""
    r = rounds["4x1"]
    assert r["lowered"] == {"scatter_gather": 1.0, "colocated": 0.0,
                            "all_reduce": 0.0}
    assert r["reduce_scatters"] == 2
    assert r["sharded"]


def test_model_axis_keeps_the_all_reduce(rounds):
    """A (2, 4) data x model mesh splits leaves over ``model`` too: the
    round lowers as before, counts ``all_reduce`` and has no
    reduce-scatter."""
    r = rounds["2x4"]
    assert r["lowered"] == {"scatter_gather": 0.0, "colocated": 0.0,
                            "all_reduce": 1.0}
    assert r["reduce_scatters"] == 0
    assert r["sharded"]


def test_two_clients_on_one_device_count_colocated(rounds):
    """Two clients on a one-device mesh: the round counts ``colocated``,
    has no collective, and on the CPU is the mean above bit for bit."""
    r = rounds["1x1"]
    assert r["lowered"] == {"scatter_gather": 0.0, "colocated": 1.0,
                            "all_reduce": 0.0}
    assert r["reduce_scatters"] == 0


@pytest.mark.parametrize("shape,dim", [
    ((4, 2560, 6400), 0),     # MiniCPM3's stacked w_gate: the layer axis
    ((4, 2560, 288), 0),
    ((73472, 2560), 1),       # its embedding: the 2,560 columns
    ((50432, 2560), 1),       # Mamba2's embedding
    ((62, 6400, 2560), 2),    # 62 layers do not split over 4: the columns
    ((4, 2560), None),        # a norm stack: 4 rows fill no (8, 128) tile
    ((8, 4, 5376), None),     # Mamba2's conv_w stack: 4 rows
    ((2560, 6400), None),     # 2-D rows are padded; 1,600 columns a shard
    ((2560,), None),
    ((9, 10), None),
])
def test_scatter_dim_by_shape(shape, dim):
    """The dimension scattered over 4 devices, by the leaf's shape: the
    ones the TPU compiler turns into a real reduce-scatter for a
    described v5e:2x2 (others fall back to an all-reduce and a slice)."""
    assert scatter_dim(shape, 4) == dim


def test_no_mesh_or_one_device_keeps_the_all_reduce():
    """Without a mesh, with one client per device on one device, or with
    two clients sharing a device, the round neither scatters nor gathers:
    its replicas share a device, so it averages them in place."""
    import jax

    one = jax.make_mesh((1, 1), ("data", "model"),
                        axis_types=(jax.sharding.AxisType.Auto,) * 2)
    assert not LS._one_replica_per_device(None, "data", 4)
    assert not LS._one_replica_per_device(one, "data", 1)
    assert not LS._one_replica_per_device(one, "data", 2)
    assert LS._colocated(None) and LS._colocated(one)
