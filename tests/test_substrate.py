"""Substrate tests: checkpointing, data pipeline, optimizers, CNNs, hlo parser."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import latest_step, load_checkpoint, save_checkpoint
from repro.data import make_binary_classification, make_multiclass_images, make_token_stream
from repro.data.partition import partition_paper
from repro.models import cnn
from repro.optim import adamw_init, adamw_update, make_optimizer, sgd_init, sgd_update


def test_checkpoint_roundtrip(tmp_path):
    tree = {"a": jnp.arange(6).reshape(2, 3).astype(jnp.bfloat16),
            "nested": {"b": jnp.ones((4,), jnp.float32)},
            "lst": [jnp.zeros((2,)), jnp.full((1,), 7.0)]}
    save_checkpoint(str(tmp_path), 42, tree, {"stage": 3, "k": 8})
    assert latest_step(str(tmp_path)) == 42
    restored, meta = load_checkpoint(str(tmp_path), tree)
    assert meta == {"stage": 3, "k": 8}
    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))


def test_checkpoint_shape_mismatch_raises(tmp_path):
    save_checkpoint(str(tmp_path), 1, {"w": jnp.zeros((3,))})
    with pytest.raises(ValueError):
        load_checkpoint(str(tmp_path), {"w": jnp.zeros((4,))})


def test_paper_partition_noniid_skew():
    """Label-sorted dealing must create skewed class distributions (s=0)."""
    x, y = make_multiclass_images(n=2000, n_classes=10)
    out = partition_paper(x, y, 8, iid_percent=0.0, seed=0)
    # each client's share should be dominated by few classes
    dominances = []
    for c in range(8):
        _, counts = np.unique(out["y"][c], return_counts=True)
        dominances.append(counts.max() / counts.sum())
    assert np.mean(dominances) > 0.5
    # while s=100 gives near-uniform
    out_iid = partition_paper(x, y, 8, iid_percent=100.0, seed=0)
    dom_iid = []
    for c in range(8):
        _, counts = np.unique(out_iid["y"][c], return_counts=True)
        dom_iid.append(counts.max() / counts.sum())
    assert np.mean(dom_iid) < 0.3


def test_token_stream_noniid_heads_differ():
    shards = make_token_stream(5000, 100, 4, seed=0, non_iid=True)
    heads = [np.bincount(s, minlength=100).argmax() for s in shards]
    assert len(set(heads)) > 1


def test_sgd_momentum_update():
    p = {"w": jnp.ones((4,))}
    st = sgd_init(p)
    g = {"w": jnp.full((4,), 2.0)}
    p1, st1 = sgd_update(p, g, st, eta=0.1, momentum=0.9)
    np.testing.assert_allclose(np.asarray(p1["w"]), 1.0 - 0.2)
    p2, st2 = sgd_update(p1, g, st1, eta=0.1, momentum=0.9)
    # m2 = 0.9*2 + 2 = 3.8 → p2 = 0.8 - 0.38
    np.testing.assert_allclose(np.asarray(p2["w"]), 0.8 - 0.38, rtol=1e-6)


def test_adamw_decreases_quadratic():
    p = {"w": jnp.asarray([5.0, -3.0])}
    st = adamw_init(p)
    loss = lambda p: jnp.sum(p["w"] ** 2)
    for _ in range(200):
        g = jax.grad(loss)(p)
        p, st = adamw_update(p, g, st, eta=0.05)
    assert float(loss(p)) < 0.1


@pytest.mark.parametrize("net", ["resnet18", "vgg16"])
def test_cnn_forward_and_grad(net):
    rng = jax.random.key(0)
    x = jax.random.normal(jax.random.key(1), (2, 32, 32, 3))
    y = jnp.asarray([1, 3])
    if net == "resnet18":
        params, strides = cnn.init_resnet18(rng, width=8)
        fwd = lambda p: cnn.apply_resnet18(p, strides, x)
    else:
        params = cnn.init_vgg16(rng, width=8)
        fwd = lambda p: cnn.apply_vgg16(p, x)
    logits = fwd(params)
    assert logits.shape == (2, 10)
    assert bool(jnp.isfinite(logits).all())
    g = jax.grad(lambda p: cnn.cross_entropy(fwd(p), y))(params)
    assert all(bool(jnp.isfinite(l).all()) for l in jax.tree.leaves(g))


def test_hlo_parser_on_synthetic_module():
    from repro.launch.hlo_analysis import parse_collectives_nested

    hlo = """HloModule test, is_scheduled=true

%cond (arg: (s32[])) -> pred[] {
  %iv = s32[] get-tuple-element(%arg), index=0
  %c = s32[] constant(5)
  ROOT %lt = pred[] compare(%iv, %c), direction=LT
}

%body (arg: (s32[])) -> (s32[]) {
  %x = f32[8,16]{1,0} parameter(0)
  %ar = f32[8,16]{1,0} all-reduce(%x), replica_groups={{0,1},{2,3}}, to_apply=%add
  ROOT %t = (s32[]) tuple(%iv)
}

ENTRY %main (p: f32[8,16]) -> f32[8,16] {
  %w = (s32[]) while(%init), condition=%cond, body=%body
  %ag = f32[16,16]{1,0} all-gather(%p), replica_groups={{0,2},{1,3}}, dimensions={0}
  ROOT %r = f32[16,16]{1,0} copy(%ag)
}
"""
    colls = parse_collectives_nested(hlo, {"data": 2, "model": 2})
    kinds = sorted(c["kind"] for c in colls)
    assert kinds == ["all-gather", "all-reduce"]
    ar = next(c for c in colls if c["kind"] == "all-reduce")
    ag = next(c for c in colls if c["kind"] == "all-gather")
    assert ar["trip_mult"] == 5.0          # inside the while: ×trip count
    assert ar["axes"] == ["model"]         # groups {0,1} vary the minor axis
    assert ag["trip_mult"] == 1.0
    assert ag["axes"] == ["data"]          # groups {0,2} vary the major axis
    assert ar["bytes"] == 8 * 16 * 4 * 5


def test_hlo_parser_prices_reduce_scatter_by_its_full_tensor():
    """A reduce-scatter's result is one shard: its ring moves (n-1)/n of
    the n-shard tensor, as the all-gather that undoes it does, and the
    pair moves what one all-reduce of that tensor moves."""
    from repro.launch.hlo_analysis import parse_collectives

    hlo = """
  %rs = f32[2,16]{1,0} reduce-scatter(%x), replica_groups={{0,1,2,3}}, dimensions={0}, to_apply=%add
  %ag = f32[8,16]{1,0} all-gather(%rs), replica_groups={{0,1,2,3}}, dimensions={0}
  %ar = f32[8,16]{1,0} all-reduce(%x), replica_groups={{0,1,2,3}}, to_apply=%add
"""
    rs, ag, ar = parse_collectives(hlo, {"data": 4})
    assert rs["bytes"] == ag["bytes"] == 8 * 16 * 4
    assert rs["link_bytes"] == ag["link_bytes"] == 8 * 16 * 4 * 3 / 4
    assert rs["link_bytes"] + ag["link_bytes"] == ar["link_bytes"]
