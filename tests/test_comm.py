"""repro.comm: reducer semantics, kernel parity, cost model, EF property.

The decisive invariants:
  * DenseMean is bit-exact with tree_mean_leading, and the reducer-threaded
    round function is bit-exact with the pre-comm-subsystem dense round
    (inline Algorithm 1 reference);
  * the Pallas quantize kernels (interpret mode) match the jnp oracles —
    int8 codes exactly, the fused dequant-mean to f32 tolerance;
  * error feedback rescues a biased compressor: naive top-k sparsification
    stalls on the synthetic logreg problem, the residual-corrected reducer
    converges to the dense objective;
  * the α–β cost model prices compressed rounds ≥ 3× below dense.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.comm import (
    DenseMean,
    NetworkModel,
    QuantizedMean,
    TopKMean,
    comm_summary,
    get_reducer,
    round_bytes,
    round_time,
)
from repro.configs.base import TrainConfig
from repro.core import local_sgd as LS
from repro.core import simulate
from repro.data import make_binary_classification, partition_iid
from repro.kernels.quantize import (
    check_tile_alignment,
    compute_scale,
    dequant_mean,
    quantize,
)
from repro.models import logreg
from repro.utils.tree import tree_broadcast_leading, tree_mean_leading


# ---------------------------------------------------------------------------
# Reducer semantics
# ---------------------------------------------------------------------------

def _stacked(seed=0, n=4):
    k1, k2 = jax.random.split(jax.random.key(seed))
    return {"w": jax.random.normal(k1, (n, 33, 7)),
            "b": jax.random.normal(k2, (n, 5))}


def test_dense_mean_bit_exact():
    stacked = _stacked()
    red = DenseMean()
    mean, state = red.reduce(stacked, red.init_state(stacked),
                             jax.random.key(1))
    ref = tree_mean_leading(stacked)
    for a, b in zip(jax.tree.leaves(mean), jax.tree.leaves(ref)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("red", [QuantizedMean(bits=8), TopKMean(frac=0.25)])
def test_compressed_reduce_flushes_to_mean(red):
    """Protocol-faithful fixed point: clients diverge once (one round of
    local progress), then idle at the broadcast consensus. Error feedback
    must flush the dropped mass so the consensus converges to the exact
    dense mean of the diverged replicas."""
    base = {"w": jax.random.normal(jax.random.key(0), (33, 7)),
            "b": jax.random.normal(jax.random.key(1), (5,))}
    offsets = _stacked(seed=2)
    stacked0 = tree_broadcast_leading(base, 4)
    state = red.init_state(stacked0)
    diverged = jax.tree.map(lambda b, o: b + 0.1 * o, stacked0, offsets)
    target = tree_mean_leading(diverged)
    mean, state = red.reduce(diverged, state, jax.random.key(3))
    for i in range(12):  # clients idle at consensus; residuals drain
        mean, state = red.reduce(tree_broadcast_leading(mean, 4), state,
                                 jax.random.key(4 + i))
    err = max(float(jnp.max(jnp.abs(a - b)))
              for a, b in zip(jax.tree.leaves(mean), jax.tree.leaves(target)))
    assert err < 1e-3, err


def test_reduce_is_scan_safe():
    stacked = _stacked()
    red = QuantizedMean(bits=4)

    def body(carry, rng):
        mean, carry = red.reduce(stacked, carry, rng)
        return carry, mean["b"].sum()

    _, out = jax.jit(lambda s: jax.lax.scan(
        body, s, jax.random.split(jax.random.key(0), 3)))(
            red.init_state(stacked))
    assert out.shape == (3,) and bool(jnp.all(jnp.isfinite(out)))


def test_get_reducer_specs():
    assert isinstance(get_reducer(None), DenseMean)
    assert isinstance(get_reducer("dense"), DenseMean)
    assert get_reducer("int4").bits == 4
    assert get_reducer("quant", quant_bits=2).bits == 2
    assert get_reducer("topk", topk_frac=0.25).frac == 0.25
    r = QuantizedMean(bits=8)
    assert get_reducer(r) is r
    with pytest.raises(ValueError):
        get_reducer("bogus")


# ---------------------------------------------------------------------------
# Round-function regression: reducer-threaded round == pre-PR dense round
# ---------------------------------------------------------------------------

def test_round_fn_dense_bit_exact_with_alg1_reference():
    """make_round_fn(reducer=DenseMean) must reproduce the original dense
    Algorithm 1 round (k vmapped SGD steps + mean over replicas) bit-for-bit,
    including the rng stream."""
    d, N, k, batch, eta = 8, 4, 3, 8, 0.2
    key = jax.random.key(0)
    data = {"x": jax.random.normal(key, (N, 64, d)),
            "y": (jax.random.normal(jax.random.fold_in(key, 1), (N, 64))
                  > 0).astype(jnp.float32)}
    params = tree_broadcast_leading({"w": jnp.zeros((d,)),
                                     "b": jnp.zeros(())}, N)
    mom = jax.tree.map(jnp.zeros_like, params)

    def wloss(p, b, center, weights):
        logit = b["x"] @ p["w"] + p["b"]
        return jnp.mean(jnp.square(logit - b["y"]))

    round_fn = simulate.make_round_fn(
        wloss, k=k, batch=batch, momentum=0.0, lr_alpha=0.0, grow=1.0,
        b0=batch, max_batch=batch)
    rng_r = jax.random.key(7)
    got_p, got_m, got_t, _ = round_fn(
        (params, mom, jnp.asarray(0.0, jnp.float32), None),
        rng_r, data, None, eta)

    # inline pre-PR reference (seed-commit make_round_fn body, dense mean)
    def local_step(c, rng_t):
        p, m, t = c

        def client(pp, mm, dd, rng):
            b = simulate._sample_batch(dd, rng, batch)
            g = jax.grad(lambda q: wloss(q, b, None, None))(pp)
            m2 = jax.tree.map(lambda a, gg: 0.0 * a + gg, mm, g)
            p2 = jax.tree.map(lambda a, mm2: a - eta * mm2, pp, m2)
            return p2, m2

        rngs = jax.random.split(rng_t, N)
        p, m = jax.vmap(client)(p, m, data, rngs)
        return (p, m, t + 1.0), None

    (ref_p, ref_m, ref_t), _ = jax.lax.scan(
        local_step, (params, mom, 0.0), jax.random.split(rng_r, k))
    ref_p = tree_broadcast_leading(tree_mean_leading(ref_p), N)
    ref_m = tree_broadcast_leading(tree_mean_leading(ref_m), N)
    for a, b in zip(jax.tree.leaves(got_p), jax.tree.leaves(ref_p)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for a, b in zip(jax.tree.leaves(got_m), jax.tree.leaves(ref_m)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert float(got_t) == float(ref_t)


# ---------------------------------------------------------------------------
# Pallas kernel parity (interpret mode on CPU)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("bits", [8, 4])
@pytest.mark.parametrize("size", [1000, 70001])
def test_quantize_kernel_matches_ref(bits, size):
    x = jax.random.normal(jax.random.key(0), (size,), jnp.float32)
    rbits = jax.random.bits(jax.random.key(1), (size,), jnp.uint32)
    s = compute_scale(x)
    q_ref = quantize(x, rbits, s, bits=bits, impl="xla")
    q_ker = quantize(x, rbits, s, bits=bits, impl="interpret")
    assert q_ref.dtype == jnp.int8
    np.testing.assert_array_equal(np.asarray(q_ref), np.asarray(q_ker))
    qmax = 2 ** (bits - 1) - 1
    assert int(jnp.max(jnp.abs(q_ref.astype(jnp.int32)))) <= qmax


def test_uniform_from_bits_equals_the_direct_cast():
    """The kernel's int32-only bits -> U[0,1) conversion (the TPU kernel
    compiler has no uint32 -> f32 cast) is the direct cast bit for bit, so
    no int8 trajectory moved with it."""
    from repro.kernels.quantize.ref import uniform_from_bits

    edges = jnp.array([0, 1, 0xFFFF, 0x10000, 0xFFFFFF, 0x1000001,
                       0x1000003, 0x7FFFFFFF, 0x80000000, 0x80000080,
                       0x80000180, 0xFFFFFF7F, 0xFFFFFF80, 0xFFFFFFFF],
                      jnp.uint32)
    rand = jax.random.bits(jax.random.key(0), (1 << 20,), jnp.uint32)
    for b in (edges, rand):
        want = np.asarray(b.astype(jnp.float32) * (1.0 / 4294967296.0))
        for view in (b, jax.lax.bitcast_convert_type(b, jnp.int32)):
            got = np.asarray(jax.jit(uniform_from_bits)(view))
            np.testing.assert_array_equal(got.view(np.uint32),
                                          want.view(np.uint32))


@pytest.mark.parametrize("bits", [8, 4])
def test_dequant_mean_kernel_matches_ref(bits):
    N, M = 5, 3000
    x = jax.random.normal(jax.random.key(0), (N, M), jnp.float32)
    rbits = jax.random.bits(jax.random.key(1), (N, M), jnp.uint32)
    scales = jnp.max(jnp.abs(x), axis=1)
    q = jnp.stack([quantize(x[i], rbits[i], scales[i], bits=bits)
                   for i in range(N)])
    m_ref = dequant_mean(q, scales, bits=bits, impl="xla")
    m_ker = dequant_mean(q, scales, bits=bits, impl="interpret")
    np.testing.assert_allclose(np.asarray(m_ref), np.asarray(m_ker),
                               rtol=1e-6, atol=1e-6)
    # fused dequant-mean approximates the true mean at int8
    if bits == 8:
        np.testing.assert_allclose(np.asarray(m_ker), np.asarray(x.mean(0)),
                                   atol=2 * float(scales.max()) / 127)


def test_quantized_mean_interpret_impl_matches_xla():
    stacked = _stacked(n=3)
    rngs = jax.random.key(5)
    out = {}
    for impl in ("xla", "interpret"):
        red = QuantizedMean(bits=8, impl=impl)
        mean, _ = red.reduce(stacked, red.init_state(stacked), rngs)
        out[impl] = mean
    for a, b in zip(jax.tree.leaves(out["xla"]),
                    jax.tree.leaves(out["interpret"])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("shape", [(5,), (33, 7), (257,), (100,)])
def test_quantize_kernel_misaligned_shapes_pad_to_int8_tile(shape):
    """Regression: inputs that don't fill a (32, 128) int8 tile are padded,
    not silently mis-tiled — and remain bit-exact with the oracle. A small
    custom block exercises the padding path rather than hiding behind the
    64K default."""
    x = jax.random.normal(jax.random.key(0), shape, jnp.float32)
    rbits = jax.random.bits(jax.random.key(1), shape, jnp.uint32)
    s = compute_scale(x)
    q_ref = quantize(x, rbits, s, impl="xla")
    q_ker = quantize(x, rbits, s, impl="interpret", block=4096)
    assert q_ker.shape == shape
    np.testing.assert_array_equal(np.asarray(q_ref), np.asarray(q_ker))
    N = 3
    xs = jnp.stack([x.reshape(-1)] * N) + jnp.arange(N)[:, None] * 0.1
    rb = jax.random.bits(jax.random.key(2), xs.shape, jnp.uint32)
    scales = jnp.max(jnp.abs(xs), axis=1)
    q = jnp.stack([quantize(xs[i], rb[i], scales[i]) for i in range(N)])
    m_ref = dequant_mean(q, scales, impl="xla")
    m_ker = dequant_mean(q, scales, impl="interpret", block=4096)
    np.testing.assert_allclose(np.asarray(m_ref), np.asarray(m_ker),
                               rtol=1e-6, atol=1e-6)


def test_quantize_kernel_rejects_misaligned_block():
    """Blocks that don't pad to whole (32, 128) int8 tiles must raise in
    every kernel mode instead of relying on interpret-mode leniency."""
    x = jax.random.normal(jax.random.key(0), (100,), jnp.float32)
    rbits = jax.random.bits(jax.random.key(1), (100,), jnp.uint32)
    s = compute_scale(x)
    assert check_tile_alignment(4096) == 4096
    assert check_tile_alignment(65536) == 65536
    for bad in (128, 1000, 4095, 4097, 0, -4096):
        with pytest.raises(ValueError):
            check_tile_alignment(bad)
        with pytest.raises(ValueError):
            quantize(x, rbits, s, impl="interpret", block=bad)
    with pytest.raises(ValueError):
        dequant_mean(jnp.zeros((2, 100), jnp.int8), jnp.ones((2,)),
                     impl="interpret", block=129)


# ---------------------------------------------------------------------------
# Error-feedback property on the synthetic logreg problem
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def logreg_problem():
    x, y = make_binary_classification(n=2048, d=32, seed=0)
    lam = 1e-2
    data = {k: jnp.asarray(v)
            for k, v in partition_iid(x, y, 4, seed=0).items()}
    xj, yj = jnp.asarray(x), jnp.asarray(y)
    loss_fn = lambda p, b: logreg.loss_fn(p, b, lam)
    eval_fn = jax.jit(lambda p: logreg.full_objective(p, xj, yj, lam))
    p0 = logreg.init_params(None, 32)
    p = p0
    g = jax.jit(jax.grad(eval_fn))
    for _ in range(2000):
        p = jax.tree.map(lambda a, b: a - 1.0 * b, p, g(p))
    return loss_fn, eval_fn, p0, data, float(eval_fn(p))


def _gap(problem, reducer):
    loss_fn, eval_fn, p0, data, fstar = problem
    cfg = TrainConfig(algo="local", eta1=0.3, T1=512, k1=4.0, n_stages=2,
                      iid=True, batch_per_client=16, seed=0)
    hist = simulate.run(loss_fn, p0, data, cfg, eval_fn, eval_every=64,
                        reducer=reducer)
    return hist[-1].value - fstar


def test_error_feedback_rescues_biased_compressor(logreg_problem):
    """Naive (no-residual) top-k sparsification of the round deltas stalls
    an order of magnitude above the optimum; the same compressor with error
    feedback converges to the dense objective."""
    gap_naive = _gap(logreg_problem, TopKMean(frac=0.03,
                                              error_feedback=False))
    gap_ef = _gap(logreg_problem, TopKMean(frac=0.03, error_feedback=True))
    gap_dense = _gap(logreg_problem, None)
    assert gap_ef < 2e-3, gap_ef
    assert gap_naive > 10 * gap_ef, (gap_naive, gap_ef)
    assert abs(gap_ef - gap_dense) < 2e-3


def test_quantized_ef_matches_dense_at_2_bits(logreg_problem):
    """Even 2-bit stochastic delta quantization with EF lands on the dense
    objective (the residual absorbs the coarse lattice)."""
    gap_q2 = _gap(logreg_problem, QuantizedMean(bits=2))
    gap_dense = _gap(logreg_problem, None)
    assert abs(gap_q2 - gap_dense) < 2e-3, (gap_q2, gap_dense)


def test_simulate_dense_reducer_arg_is_default():
    """reducer=DenseMean() and the default path produce identical traces."""
    x, y = make_binary_classification(n=512, d=8, seed=0)
    data = {k: jnp.asarray(v)
            for k, v in partition_iid(x, y, 2, seed=0).items()}
    loss_fn = lambda p, b: logreg.loss_fn(p, b, 1e-2)
    xj, yj = jnp.asarray(x), jnp.asarray(y)
    eval_fn = jax.jit(lambda p: logreg.full_objective(p, xj, yj, 1e-2))
    p0 = logreg.init_params(None, 8)
    cfg = TrainConfig(algo="stl_sc", eta1=0.2, T1=16, k1=2.0, n_stages=3,
                      iid=True, batch_per_client=8, seed=0)
    h1 = simulate.run(loss_fn, p0, data, cfg, eval_fn)
    h2 = simulate.run(loss_fn, p0, data, cfg, eval_fn, reducer=DenseMean())
    assert [(r.round, r.value) for r in h1] == \
        [(r.round, r.value) for r in h2]


# ---------------------------------------------------------------------------
# Distributed sync_step + cost model
# ---------------------------------------------------------------------------

def test_build_sync_step_dense_preserves_contract():
    params = _stacked(n=4)
    state = {"params": params,
             "opt": {"mu": jnp.zeros((4, 33, 7))},
             "step": jnp.zeros((), jnp.int32)}
    out = jax.jit(LS.build_sync_step())(state)
    assert set(out.keys()) == {"params", "opt", "step"}
    np.testing.assert_array_equal(
        np.asarray(out["params"]["w"][0]),
        np.asarray(tree_mean_leading(params)["w"]))


def test_build_sync_step_compressed_round():
    params = _stacked(n=4)
    state = {"params": params,
             "opt": {"mu": jnp.zeros((4, 33, 7))},
             "step": jnp.zeros((), jnp.int32)}
    sync = LS.build_sync_step("int8")
    out = jax.jit(sync)(state)
    assert "comm" in out
    # replicas agree post-sync and sit near the dense mean
    np.testing.assert_array_equal(np.asarray(out["params"]["w"][0]),
                                  np.asarray(out["params"]["w"][1]))
    err = float(jnp.max(jnp.abs(out["params"]["w"][0]
                                - tree_mean_leading(params)["w"])))
    assert err < 0.1, err
    jax.jit(sync)(out)  # second round with comm state threaded


def test_train_sync_loop_threads_comm_state():
    """Regression: train_step_local must not drop the "comm" key — otherwise
    a compressed sync re-initializes its error-feedback residuals (and
    reference) from the diverged replicas every round, silently degrading to
    the naive compressor. Drives the real build_train_steps/build_sync_step
    pair for two full train->sync rounds."""
    from repro.configs.base import ArchConfig

    cfg = ArchConfig()  # loss_fn below ignores it
    C, d = 3, 16

    def toy_loss(params, _cfg, batch):
        return jnp.mean(jnp.square(batch["x"] @ params["w"] - batch["y"]))

    train_step, sync_step, _ = LS.build_train_steps(
        cfg, None, loss_fn=toy_loss, reducer="int8")
    assert sync_step.topology.reducer.name == "int8"
    key = jax.random.key(0)
    state = {"params": tree_broadcast_leading(
                 {"w": jax.random.normal(key, (d,))}, C),
             "opt": {"mu": {"w": jnp.zeros((C, d))}},
             "step": jnp.zeros((), jnp.int32)}
    batch = {"x": jax.random.normal(jax.random.fold_in(key, 1), (C, 8, d)),
             "y": jax.random.normal(jax.random.fold_in(key, 2), (C, 8))}
    state, _ = train_step(state, batch, 0.1)
    state = sync_step(state)
    assert "comm" in state
    # EF is live: the quantizer's residual is nonzero after a real round
    assert float(jnp.max(jnp.abs(state["comm"]["res"]["w"]))) > 0.0
    state, _ = train_step(state, batch, 0.1)
    assert "comm" in state, "train_step_local dropped the comm state"
    state = sync_step(state)
    # the reference tracks the broadcast consensus exactly
    np.testing.assert_array_equal(np.asarray(state["comm"]["ref"]["w"]),
                                  np.asarray(state["params"]["w"][0]))
    # and the driver prices the reducer the sync_step's topology carries
    from repro.core.stl_sgd import StagewiseDriver

    drv = StagewiseDriver(TrainConfig(algo="local", T1=4, k1=2.0, n_stages=1),
                          train_step, jax.jit(sync_step))
    assert drv.topology.reducer.name == "int8"


def test_cost_model_prices_compression():
    tmpl = {"w": jax.ShapeDtypeStruct((1000,), jnp.float32)}
    net = NetworkModel(latency_s=1e-2, bandwidth_gbps=1.0)
    dense_b = round_bytes(DenseMean(), tmpl, 8, net)
    int8_b = round_bytes(QuantizedMean(bits=8), tmpl, 8, net)
    topk_b = round_bytes(TopKMean(frac=0.1), tmpl, 8, net)
    assert dense_b == 8 * 4000
    assert dense_b / int8_b > 3.0
    assert dense_b / topk_b > 3.0
    assert round_time(net, 0) == pytest.approx(1e-2)
    assert round_time(net, net.bandwidth_Bps) == pytest.approx(1.0 + 1e-2)
    summ = comm_summary(QuantizedMean(bits=8), tmpl, 8, 10, net)
    assert summ["total_bytes"] == summ["bytes_per_round"] * 10
    assert summ["reducer"] == "int8"
