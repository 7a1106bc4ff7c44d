#!/usr/bin/env python3
"""Smoke run of the stagewise Local SGD main path on TPU chips.

One process drives the system through its own entry points and checks
what comes out; every phase prints its findings on its own lines.

  device  refuse anything but a TPU: there is no CPU fallback
  train   ``repro.launch.train.main``: MiniCPM3-4B at published widths cut
          to 4 layers, 2 clients x 1 x 2048 tokens, momentum 0.9, stl_sc
          with k growing 2 -> 4 over 12 local steps and 4 sync rounds,
          writing a serveable checkpoint. Then one more local step and a
          dense round, checked against an f32 host mean of the pre-sync
          replicas.
  int8    the int8 round on the compiled Pallas kernels against the XLA
          oracle, on the same state and rng: codes and params bit for bit
  serve   ``repro.launch.serve.main`` on the checkpoint: 8 requests, 4 slots

``--chips 4`` runs only the four-chip phase: 4 clients sharded over
``data=4`` (per-device bytes, a dense round against the host mean) and the
two-level round on ``(pod=2, data=2)`` against ``engine.Hierarchical.reduce``
on the host CPU.

Compile seconds are what XLA spent compiling (or reading the persistent
compilation cache), from ``jax.monitoring``; step seconds are host seconds
from a step's launch to ``jax.block_until_ready`` on its state, read from
the launcher's span log (``--trace``): a local step's ``dispatch`` and
``wait`` spans, a round's ``reduce`` span. The last line of stdout is
``{"ok": true, "device": {...}}``, printed only when every check passed.

  python chip_smoke.py
  python chip_smoke.py --chips 4
"""
from __future__ import annotations

import argparse
import json
import math
import os
import statistics
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))
# the library's JSON progress log would bury the checks printed below
os.environ.setdefault("REPRO_LOG_LEVEL", "warning")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import NamedSharding  # noqa: E402

from repro.comm.reducer import DenseMean, QuantizedMean, get_reducer  # noqa: E402
from repro.configs import get_arch  # noqa: E402
from repro.core import local_sgd as LS  # noqa: E402
from repro.engine.topology import Hierarchical  # noqa: E402
from repro.kernels.quantize import ops as Q  # noqa: E402
from repro.launch import serve as serve_cli  # noqa: E402
from repro.launch import train as train_cli  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.mesh import make_client_mesh  # noqa: E402
from repro.obs import WALL, read_jsonl  # noqa: E402

ARCH, LAYERS, SEQ, MOMENTUM, ETA1 = "minicpm3-4b", 4, 2048, 0.9, 0.01
OUT = ROOT / "artifacts" / "chip_smoke"
# First training loss of the smoke (seed 0, both clients' first batch):
# the f32-accumulated CPU forward of the same params and batch gives
# 31.4268. It sits near ln V + 20, not ln V = 11.20, because the embedding
# is tied: the last hidden state still carries the input token's
# embedding, whose logit (~|h||e| ~ 36) dominates the softmax. Across data
# seeds the same forward spreads over 29.95..31.48; bf16 rounding on the
# chip moves it far less, so 1.0 separates numerics from a wrong path
# (uniform logits would read 11.2).
FIRST_LOSS, FIRST_LOSS_TOL = 31.4268, 1.0
BF16_U = 2.0 ** -8       # unit roundoff of bf16 (8 significand bits)
BF16_SPACING = 2.0 ** -7  # bf16 value spacing relative to the value


def say(phase: str, **fields):
    print(f"[{phase}] " + " ".join(f"{k}={v}" for k, v in fields.items()),
          flush=True)


def check(ok: bool, what: str):
    say("check", result="pass" if ok else "FAIL", what=json.dumps(what))
    if not ok:
        raise SystemExit(f"chip_smoke: check failed: {what}")


class CompileClock:
    """Seconds XLA spent compiling, persistent-cache reads included."""

    def __init__(self):
        self.s = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.s += duration

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


def memory(phase: str):
    stats = [d.memory_stats() for d in jax.devices()]
    say(phase, peak_bytes_in_use=[s["peak_bytes_in_use"] for s in stats],
        bytes_in_use=[s["bytes_in_use"] for s in stats])
    return stats


def tree_bytes(tree) -> int:
    return sum(x.nbytes for x in jax.tree.leaves(tree))


def free(tree):
    for x in jax.tree.leaves(tree):
        x.delete()


def bits(x):
    """The array's raw bits, for bit-for-bit comparison."""
    x = np.asarray(x)
    return x.view(np.dtype(f"u{x.dtype.itemsize}"))


@jax.jit
def replicas_identical(tree):
    def leaf(x):
        b = jax.lax.bitcast_convert_type(
            x, jnp.dtype(f"uint{8 * x.dtype.itemsize}"))
        return jnp.all(b == b[:1])

    return jnp.all(jnp.stack([leaf(x) for x in jax.tree.leaves(tree)]))


def step_seconds(spans):
    """Host seconds of each local step (its ``dispatch`` and ``wait``
    spans) and of each round (its ``reduce`` span), from a span log."""
    wall = [s for s in spans if s.clock == WALL]
    steps = {s.id: 0.0 for s in wall if s.name == "step"}
    for s in wall:
        if s.parent in steps and s.name in ("dispatch", "wait"):
            steps[s.parent] += s.duration
    return (list(steps.values()),
            [s.duration for s in wall if s.name == "reduce"])


def run_train(clock, argv, phase):
    """``launch.train.main`` with timings and losses pulled from its
    stage results and span log."""
    trace = OUT / f"{phase}.trace.json"
    c0, h0, t0 = clock.s, clock.cache_hits, time.perf_counter()
    ds = train_cli.main(argv + ["--trace", str(trace)])
    wall = time.perf_counter() - t0
    steps, syncs = step_seconds(read_jsonl(f"{trace}l"))
    losses = [x for r in ds.results for x in r.losses]
    say(phase, k_per_stage=[r.k for r in ds.results],
        rounds_per_stage=[r.rounds for r in ds.results],
        iters=ds.iters_total, rounds=ds.rounds_total)
    say(phase, losses=[round(x, 4) for x in losses],
        ln_vocab=round(math.log(get_arch(ARCH).vocab_size), 4))
    say(phase, compile_s=round(clock.s - c0, 3),
        cache_hits=clock.cache_hits - h0,
        first_step_s=round(steps[0], 4),
        steady_step_s=round(statistics.median(steps[1:]), 4)
        if len(steps) > 1 else None,
        first_sync_s=round(syncs[0], 4),
        steady_sync_s=round(statistics.median(syncs[1:]), 4)
        if len(syncs) > 1 else None,
        wall_s=round(wall, 3))
    check(all(math.isfinite(x) for x in losses) and len(losses) == ds.iters_total,
          f"all {ds.iters_total} step losses finite")
    check(bool(replicas_identical(ds.state["params"])),
          "replicas bit-identical right after the run's last sync")
    return ds


def dense_round(cfg, mesh, state, seed, phase):
    """One more local step (replicas diverge), then the dense round: the
    replicas must come out bit-identical and equal to the f32 host mean of
    the pre-sync replicas within one bf16 rounding. Returns the post-sync
    state and the host copy of the pre-sync params."""
    n = jax.tree.leaves(state["params"])[0].shape[0]
    train_local, sync, _ = LS.build_train_steps(
        cfg, mesh, client_axis="data", momentum=MOMENTUM)
    bsh = {k: NamedSharding(mesh, s)
           for k, s in LS.batch_spec(cfg, "data", False).items()}
    batch = jax.device_put(next(train_cli.synthetic_batches(
        cfg, n, 1, SEQ, seed)), bsh)
    state, _ = jax.jit(train_local, donate_argnums=(0,))(state, batch, ETA1)
    pre = jax.device_get(state["params"])
    t0 = time.perf_counter()
    state = jax.block_until_ready(
        jax.jit(sync, donate_argnums=(0,))(state))
    say(phase, dense_round_s=round(time.perf_counter() - t0, 4),
        note="includes compile")
    check(bool(replicas_identical(state["params"])),
          "dense round: replicas bit-identical")
    worst = 0.0
    for x, got in zip(jax.tree.leaves(pre),
                      jax.tree.leaves(jax.device_get(state["params"]))):
        x32 = x.astype(np.float32)
        want = x32.mean(axis=0)
        # one bf16 rounding of the mean, plus f32 summation-order slack
        tol = BF16_U * np.abs(want) + 2.0 ** -22 * np.abs(x32).mean(axis=0)
        err = np.abs(got[0].astype(np.float32) - want)
        worst = max(worst, float(np.max(err / np.maximum(tol, 1e-30))))
    say(phase, dense_round_err_over_tol=round(worst, 4))
    check(worst <= 1.0, "dense round = f32 host mean of the pre-sync "
                        "replicas within one bf16 rounding")
    return state, pre


def phase_one_chip(clock):
    cfg = get_arch(ARCH, layers=LAYERS)
    ckpt = OUT / "ckpt"
    ds = run_train(clock, [
        "--arch", ARCH, "--layers", str(LAYERS), "--clients", "2",
        "--batch", "1", "--seq", str(SEQ), "--momentum", str(MOMENTUM),
        "--algo", "stl_sc", "--eta1", str(ETA1), "--k1", "2", "--T1", "4",
        "--stages", "2", "--steps", "12", "--seed", "0",
        "--ckpt-out", str(ckpt)], "train")
    losses = [x for r in ds.results for x in r.losses]
    check([r.k for r in ds.results] == [2, 4]
          and [r.rounds for r in ds.results] == [2, 2],
          "stl_sc schedule: k 2 -> 4, two rounds per stage")
    check(abs(losses[0] - FIRST_LOSS) <= FIRST_LOSS_TOL,
          f"first loss {losses[0]:.4f} within {FIRST_LOSS_TOL} of the CPU "
          f"forward's {FIRST_LOSS}")
    memory("train")
    state, step = ds.state, int(ds.state["step"])
    consensus = jax.device_get(jax.tree.map(lambda x: x[0], state["params"]))
    del ds
    state, pre = dense_round(cfg, make_client_mesh(2), state, 1, "train")
    free(state)
    del state

    # int8: the same diverged replicas, consensus reference and rng
    # through the compiled Pallas kernels and the XLA oracle
    def int8_state():
        params = jax.device_put(pre)
        return {"params": params, "opt": {}, "step": jnp.int32(step),
                "comm": {"ref": jax.device_put(jax.tree.map(
                             lambda c: c.astype(np.float32), consensus)),
                         "res": jax.tree.map(
                             lambda x: jnp.zeros(x.shape, jnp.float32),
                             params)}}

    outs = {}
    for impl in ("pallas", "xla"):
        c0 = clock.s
        sync = jax.jit(LS.build_sync_step(QuantizedMean(impl=impl)),
                       donate_argnums=(0,))
        t0 = time.perf_counter()
        out = jax.block_until_ready(sync(int8_state()))
        say("int8", impl=impl, round_s=round(time.perf_counter() - t0, 4),
            compile_s=round(clock.s - c0, 3))
        outs[impl] = jax.device_get({"params": out["params"],
                                     "comm": out["comm"]})
        free(out)
    same = all(np.array_equal(bits(a), bits(b)) for a, b in zip(
        jax.tree.leaves(outs["pallas"]), jax.tree.leaves(outs["xla"])))
    check(same, "int8 round: params, reference and residuals from the "
                "Pallas kernels equal the XLA oracle's bit for bit")
    del outs
    # the codes themselves, on the largest leaf (the embedding)
    emb = pre["embed"].astype(np.float32)
    y = jnp.asarray((emb - consensus["embed"].astype(np.float32)[None])
                    .reshape(emb.shape[0], -1))
    scales = jnp.maximum(jnp.max(jnp.abs(y), axis=1), 1e-12)
    rbits = jax.random.bits(jax.random.key(step), y.shape, jnp.uint32)
    codes = {impl: bits(jax.jit(lambda a, b, c, impl=impl: Q.encode_leaf(
        a, b, c, impl=impl))(y, rbits, scales)) for impl in ("pallas", "xla")}
    say("int8", embed_codes_nonzero=int(np.count_nonzero(codes["xla"])),
        embed_codes=codes["xla"].size)
    check(np.array_equal(codes["pallas"], codes["xla"]),
          "int8 codes of the embedding: Pallas = XLA bit for bit")
    del y, rbits, codes
    memory("int8")

    c0, t0 = clock.s, time.perf_counter()
    report = serve_cli.main(["--ckpt", str(ckpt), "--requests", "8",
                             "--slots", "4", "--max-seq-len", "512"])
    done = report.completed
    say("serve", completed=len(done), rejected=len(report.rejected),
        decode_steps=report.n_steps, prefills=report.n_prefills,
        tokens=sum(len(r.tokens) for r in done),
        wall_s=round(report.measured_wall_s, 3),
        compile_s=round(clock.s - c0, 3),
        total_s=round(time.perf_counter() - t0, 3))
    vocab = cfg.vocab_size
    check(len(done) == 8 and not report.rejected,
          "serve: all 8 requests completed, none rejected")
    check(all(len(r.tokens) == r.n_out and all(0 <= t < vocab
                                               for t in r.tokens)
              for r in done),
          "serve: every request got its n_out tokens, all in the vocab")
    memory("serve")


def phase_four_chips(clock):
    cfg = get_arch(ARCH, layers=LAYERS)
    ds = run_train(clock, [
        "--arch", ARCH, "--layers", str(LAYERS), "--clients", "4",
        "--batch", "1", "--seq", str(SEQ), "--momentum", str(MOMENTUM),
        "--algo", "stl_sc", "--eta1", str(ETA1), "--k1", "2", "--T1", "2",
        "--stages", "1", "--steps", "2", "--seed", "0"], "flat4")
    state, step = ds.state, int(ds.state["step"])
    del ds
    total = tree_bytes(state)
    used = [s["bytes_in_use"] for s in memory("flat4")]
    shard_dims = sorted({x.sharding.shard_shape(x.shape)[0]
                         for x in jax.tree.leaves(state) if x.ndim})
    say("flat4", state_bytes=total,
        share_per_device=[round(u / total, 4) for u in used],
        client_shard_dims=shard_dims)
    check(shard_dims == [1] and all(0.2 <= u / total <= 0.4 for u in used),
          "each device holds one client's replica, about a quarter of the "
          "state")
    state, pre = dense_round(cfg, make_client_mesh(4), state, 1, "flat4")
    free(state)
    del state
    memory("flat4")

    # two-level round: dense intra-pod over data, int8 inter-pod over pod
    mesh = make_client_mesh(4, pods=2)
    axis = ("pod", "data")
    shapes = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                          pre)
    sh = LS.state_shardings(cfg, mesh, shapes, {}, client_axis=axis)
    sync = jax.jit(LS.build_sync_step("dense", hierarchical=True, n_pods=2,
                                      inter_reducer="int8", mesh=mesh,
                                      client_axis=axis), donate_argnums=(0,))
    c0, t0 = clock.s, time.perf_counter()
    out = jax.block_until_ready(sync({
        "params": jax.device_put(pre, sh["params"]), "opt": {},
        "step": jnp.int32(step)}))
    say("hier4", mesh=dict(mesh.shape),
        round_s=round(time.perf_counter() - t0, 4),
        compile_s=round(clock.s - c0, 3))
    check(bool(replicas_identical(out["params"])),
          "two-level round: replicas bit-identical")
    got = jax.device_get(jax.tree.map(lambda x: x[0], out["params"]))
    res = jax.device_get(out["comm"]["inter"]["res"])
    free(out)
    topo = Hierarchical(n_pods=2, intra=DenseMean(),
                        inter=get_reducer("int8"))
    t0 = time.perf_counter()
    with jax.default_device(jax.devices("cpu")[0]):
        rng = jax.random.fold_in(jax.random.key(0), step)
        want, st = jax.jit(
            lambda p: topo.reduce(p, topo.init_state(p), rng))(
            jax.device_put(pre))
        want, want_res = jax.device_get((want, st["inter"]["res"]))
    say("hier4", cpu_reference_s=round(time.perf_counter() - t0, 3))
    # The inter hop quantises pod 1's delta from pod 0 (pod 0's is zero)
    # with weight w = max|delta| / qmax per leaf; its residual is
    # delta - code * w, so the residuals' difference counts the codes the
    # chip and the CPU rounded apart (f32 division is not correctly
    # rounded on the chip). One code moves the consensus of 2 pods by
    # q = w / 2 in f32; both consensi are then rounded to bf16, each by at
    # most 2^-8 of its value, so |got - want| <= q (1 + 2^-8) + 2^-7 |want|
    # / (1 - 2^-8).
    qmax = 127.0
    worst, n_off, codes_off, max_code_off = 0.0, 0, 0, 0
    for x, g, w, r_chip, r_cpu in zip(
            jax.tree.leaves(pre), jax.tree.leaves(got), jax.tree.leaves(want),
            jax.tree.leaves(res), jax.tree.leaves(want_res)):
        pm = x.astype(np.float32).reshape((2, 2) + x.shape[1:]).mean(
            axis=1).astype(x.dtype).astype(np.float32)
        wq = max(float(np.max(np.abs(pm[1] - pm[0]))) / qmax, 1e-30)
        k = np.rint((r_cpu[1] - r_chip[1]) / wq)
        codes_off += int(np.count_nonzero(k))
        max_code_off = max(max_code_off, int(np.max(np.abs(k))))
        w32, g32 = w.astype(np.float32), g.astype(np.float32)
        err = np.abs(g32 - w32)
        tol = (wq / 2 * (1 + BF16_U)
               + 2 * BF16_U * np.abs(w32) / (1 - BF16_U))
        worst = max(worst, float(np.max(err / np.maximum(tol, 1e-30))))
        n_off += int(np.count_nonzero(err))
    say("hier4", codes_rounded_apart=codes_off,
        max_code_difference=max_code_off, err_over_tol=round(worst, 4),
        consensus_elements_not_bit_equal=n_off,
        elements=sum(x[0].size for x in jax.tree.leaves(pre)))
    check(max_code_off <= 1 and worst <= 1.0,
          "two-level round = Hierarchical.reduce on the host CPU within "
          "one quantisation step")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="Smoke run of the main path on TPU chips.")
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: train, int8 and serve on one chip; 4: only "
                         "the four-chip phase (sharded clients, flat and "
                         "two-level rounds)")
    args = ap.parse_args(argv)
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        raise SystemExit(f"chip_smoke: needs a TPU, but JAX's first device "
                         f"is {dev.platform!r} ({dev.device_kind}); there "
                         f"is no CPU fallback")
    if len(devices) != args.chips:
        raise SystemExit(f"chip_smoke: --chips {args.chips} needs exactly "
                         f"{args.chips} TPU devices, found {len(devices)}")
    cache = enable_compile_cache()
    say("device", platform=dev.platform, kind=json.dumps(dev.device_kind),
        count=len(devices), jax=jax.__version__, compile_cache=cache)
    OUT.mkdir(parents=True, exist_ok=True)
    clock = CompileClock()
    t0 = time.perf_counter()
    (phase_one_chip if args.chips == 1 else phase_four_chips)(clock)
    say("done", compile_s=round(clock.s, 3), cache_hits=clock.cache_hits,
        wall_s=round(time.perf_counter() - t0, 3))
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
