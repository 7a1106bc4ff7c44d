"""Plain reference of the first local steps and rounds of a cell.

Imports nothing of the program and takes nothing it made. The weights
come from the seed through the benchmark's own layout of the
configuration (``param_layout``), exactly as the program was handed them;
the batches are the same host batches. The model is the configuration's
layer blocks (``bench/blocks``) in float32 at ``highest`` matmul
precision, the step is momentum SGD on float32 gradients with the
parameters kept in the configuration's dtype, and the round is the plain
mean over clients of parameters and momenta.

``precision="fp8"`` is the control: what the configuration holds in
bfloat16 held in float8 e4m3 (scaled per tensor), the precision step
below: the weights, at the start and after every update and round, and
the operands of every matmul of the forward and the backward pass. ``fault`` plants a fault in the reference put in
the program's place: ``"half_batch"`` takes the loss over the first half
of each row's tokens only, ``"no_round"`` leaves the averaging out.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench.blocks.common import rms_norm
from bench.harness import data
from bench.harness.yardstick import blocks_of

HIGHEST = jax.lax.Precision.HIGHEST
E4M3_MAX = 448.0


def mm_f32(spec, a, b):
    return jnp.einsum(spec, a.astype(jnp.float32), b.astype(jnp.float32),
                      precision=HIGHEST)


def _to_fp8(x):
    """``x`` rounded to float8 e4m3 at a per-tensor scale (as float32)."""
    x = x.astype(jnp.float32)
    s = jnp.max(jnp.abs(x)) / E4M3_MAX + 1e-30
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def mm_fp8(spec, a, b):
    """A matmul with every operand in float8, forward and backward: the
    inputs, and the incoming gradient of each backward product."""
    return jnp.einsum(spec, _to_fp8(a), _to_fp8(b), precision=HIGHEST)


def _mm_fp8_fwd(spec, a, b):
    return mm_fp8(spec, a, b), (a, b)


def _mm_fp8_bwd(spec, res, g):
    a, b = res
    qa, qb, qg = _to_fp8(a), _to_fp8(b), _to_fp8(g)
    _, vjp = jax.vjp(lambda x, y: jnp.einsum(spec, x, y, precision=HIGHEST),
                     qa, qb)
    da, db = vjp(qg)
    return da.astype(a.dtype), db.astype(b.dtype)


mm_fp8.defvjp(_mm_fp8_fwd, _mm_fp8_bwd)


MATMULS = {"f32": mm_f32, "fp8": mm_fp8}


def param_layout(config: dict):
    """The program's parameter tree for ``config`` as ShapeDtypeStructs:
    embedding rows as run, the layer blocks stacked over groups of the
    block pattern, and no leading dense or trailing layers."""
    pattern = config["block_pattern"]
    if config["n_layers"] % len(pattern):
        raise ValueError("the layout covers whole periods of the pattern only")
    groups = config["n_layers"] // len(pattern)
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, jnp.dtype(dt))  # noqa: E731
    blocks = {}
    for i, kind in enumerate(pattern):
        sub = {}
        for b in blocks_of(config, kind):
            sub.update(b.param_shapes(config))
        blocks[f"sub{i}"] = jax.tree.map(
            lambda t: sds((groups,) + t[0], t[1]), sub,
            is_leaf=lambda t: isinstance(t, tuple) and isinstance(t[0], tuple))
    d, rows, w = config["d_model"], config["embed_rows"], config["dtype"]
    layout = {"embed": sds((rows, d), w), "final_norm": sds((d,), w),
              "head": [], "blocks": blocks, "tail": []}
    if not config["tie_embeddings"]:
        layout["unembed"] = sds((d, rows), w)
    return layout


def lm_loss(params, tokens, labels, config, mm, half=False):
    """Mean next-token cross-entropy of float32 ``params`` on (B, S) rows."""
    d, V = config["d_model"], config["vocab_size"]
    x = params["embed"][tokens] * np.sqrt(d)
    pattern = config["block_pattern"]

    @jax.checkpoint
    def group(x, p):                    # one period of the pattern
        for i, kind in enumerate(pattern):
            for b in blocks_of(config, kind):
                x = b.apply(p[f"sub{i}"], x, config, mm)
        return x, None

    x, _ = jax.lax.scan(group, x, params["blocks"])
    x = rms_norm(x, params["final_norm"], config["norm_eps"])
    if config["tie_embeddings"]:
        logits = mm("bsd,vd->bsv", x, params["embed"][:V])
    else:
        logits = mm("bsd,dv->bsv", x, params["unembed"][:, :V])
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    if half:
        nll = nll[:, : nll.shape[1] // 2]
    return jnp.mean(nll)


class Reference:
    def __init__(self, config: dict, traffic: dict, precision="f32",
                 fault=None):
        self.config, self.traffic, self.fault = config, traffic, fault
        mm = MATMULS[precision]
        self.layout = param_layout(config)
        mom = float(traffic["momentum"])
        low = jnp.dtype(config["dtype"])

        def store(x, held):
            """A weight as the run holds it, given the dtype the layout
            holds it in: in that dtype, or (control) on the float8 grid
            where that is the configuration's bfloat16."""
            if precision == "fp8" and held.dtype == low:
                return _to_fp8(x)
            return x.astype(held.dtype)

        def step(p, m, tokens, labels, eta):
            f32 = jax.tree.map(lambda a: a.astype(jnp.float32), p)
            with jax.default_matmul_precision("highest"):
                loss, g = jax.value_and_grad(lm_loss)(
                    f32, tokens, labels, config, mm, fault == "half_batch")
            m = g if m is None else jax.tree.map(
                lambda mi, gi: mom * mi + gi, m, g)          # from zero: m = g
            p = jax.tree.map(lambda h, f, mi: store(f - eta * mi, h),
                             self.layout, f32, m)
            return p, m, loss

        def mean(xs, held):
            return store(jnp.mean(jnp.stack([x.astype(jnp.float32)
                                             for x in xs]), axis=0), held)

        self.step = jax.jit(step)
        self.init = jax.jit(lambda key: jax.tree.map(
            store, data.init_params(key, self.layout), self.layout))
        self.mean = jax.jit(mean, static_argnums=(1,))
        self.norms = jax.jit(lambda t: jax.tree.map(
            lambda x: jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)))), t))
        self.change = jax.jit(lambda p, p0: jax.tree.map(
            lambda a, b: jnp.sqrt(jnp.sum(jnp.square(
                a.astype(jnp.float32) - b.astype(jnp.float32)))), p, p0))

    def run(self, seed: int, devices):
        """The check's numbers from the reference's first steps: per-step
        losses (mean over clients), and per (leaf, client) the first
        gradient's norm and the change after the last check step."""
        t, cfg = self.traffic, self.config
        C, n, k1 = t["clients"], t["check_steps"], int(t["k1"])
        key = data.jax_key(seed)
        batches = data.host_batches(seed, t, cfg["vocab_size"], n)
        dev = [devices[c % len(devices)] for c in range(C)]
        p0 = {d: self.init(jax.device_put(key, d)) for d in set(dev)}
        p = [p0[dev[c]] for c in range(C)]
        m = [None] * C
        losses, grad, change = [], None, None
        for i in range(n):
            b, step_losses = batches[i], []
            for c in range(C):                  # one client at a time: fits
                p[c], m[c], loss = self.step(
                    p[c], m[c], jax.device_put(b["tokens"][c], dev[c]),
                    jax.device_put(b["labels"][c], dev[c]), float(t["eta1"]))
                step_losses.append(float(loss))
            losses.append(float(np.mean(step_losses)))
            if i == 0:
                grad = _per_client([jax.device_get(self.norms(mc)) for mc in m])
            if i == n - 1:
                change = _per_client([jax.device_get(
                    self.change(p[c], p0[dev[c]])) for c in range(C)])
            elif (i + 1) % k1 == 0 and self.fault != "no_round":
                p = self._average(p, dev, self.layout)
                m = self._average(m, dev, jax.tree.map(
                    lambda h: jax.ShapeDtypeStruct(h.shape, jnp.float32),
                    self.layout))
        return {"loss": losses, "grad": grad, "change": change}

    def _average(self, trees, dev, held):
        """Mean over clients, leaf by leaf, in float32, stored as ``held``
        says each leaf is held, on each client's device."""
        def one(h, *xs):
            mean = self.mean([jax.device_put(x, dev[0]) for x in xs], h)
            return [jax.device_put(mean, d) for d in dev]

        leaves = [jax.tree.leaves(t) for t in trees]
        treedef = jax.tree.structure(trees[0])
        per_leaf = [one(h, *xs) for h, *xs in zip(jax.tree.leaves(held), *leaves)]
        return [jax.tree.unflatten(treedef, [pl[c] for pl in per_leaf])
                for c in range(len(trees))]


def _per_client(trees):
    """A list of per-client trees of scalars -> one tree of (C,) arrays."""
    return jax.tree.map(lambda *xs: np.asarray(xs, np.float64), *trees)


