"""Training launcher.

Runs STL-SGD (or a baseline) on synthetic LM data over every device
present: client replicas are spread over a ``data`` mesh axis (a
``(pod, data)`` grid for ``--topology hier``), the state is initialised
straight into its shardings and donated to the jitted local and sync
steps. ``--smoke`` picks the reduced config for CPU runs; ``--layers N``
cuts a published config to N layers at full width (recorded in the
checkpoint meta).

Examples:
  PYTHONPATH=src python -m repro.launch.train --arch qwen3-14b --smoke \
      --algo stl_sc --eta1 0.05 --k1 4 --T1 32 --stages 3 --steps 200
  # MiniCPM3-4B at published widths, 4 layers, on one v5e chip
  PYTHONPATH=src python -m repro.launch.train --arch minicpm3-4b \
      --layers 4 --clients 2 --batch 1 --seq 2048 --momentum 0.9
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding

from repro.checkpoint import save_checkpoint
from repro.configs import SHAPES, get_arch
from repro.configs.base import ShapeConfig, TrainConfig
from repro.core import local_sgd as LS
from repro.core.stl_sgd import StagewiseDriver
from repro.data.synthetic import make_token_stream
from repro.engine import algorithm_names, topology_for
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_client_mesh
from repro.utils.logging import get_logger

log = get_logger("train")


def synthetic_batches(cfg, n_clients, batch_per_client, seq_len, seed=0,
                      non_iid=False):
    """Infinite (C, B, S) host token/label batches from per-client shards."""
    shards = make_token_stream(200_000, cfg.vocab_size, n_clients, seed=seed,
                               non_iid=non_iid)
    rng = np.random.RandomState(seed)
    fe_rng = np.random.RandomState(seed + 1)
    n = shards.shape[1] - seq_len - 1
    while True:
        starts = rng.randint(0, n, size=(n_clients, batch_per_client))
        toks = np.stack([
            np.stack([shards[c, s: s + seq_len] for s in starts[c]])
            for c in range(n_clients)])
        labs = np.stack([
            np.stack([shards[c, s + 1: s + seq_len + 1] for s in starts[c]])
            for c in range(n_clients)])
        batch = {"tokens": toks, "labels": labs}
        if cfg.frontend:
            batch["frontend"] = fe_rng.randn(
                n_clients, batch_per_client, cfg.n_frontend_tokens,
                cfg.frontend_dim).astype(jnp.bfloat16)
        yield batch


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--layers", type=int, default=None, metavar="N",
                    help="cut the config to its first N layers, widths "
                         "unchanged")
    ap.add_argument("--algo", default="stl_sc",
                    choices=list(algorithm_names()))
    ap.add_argument("--clients", type=int, default=4)
    ap.add_argument("--batch", type=int, default=2)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--eta1", type=float, default=0.05)
    ap.add_argument("--k1", type=float, default=4)
    ap.add_argument("--T1", type=int, default=32)
    ap.add_argument("--stages", type=int, default=3)
    ap.add_argument("--steps", type=int, default=None)
    ap.add_argument("--non-iid", action="store_true")
    ap.add_argument("--gamma-inv", type=float, default=0.0)
    ap.add_argument("--optimizer", default="sgd")
    ap.add_argument("--momentum", type=float, default=0.0)
    ap.add_argument("--reducer", default="dense",
                    help="communication reducer: dense | int8 | int<b> | topk")
    ap.add_argument("--topology", default="star",
                    choices=["star", "streaming", "hier"],
                    help="sync round shape: flat star | per-leaf streaming "
                         "| two-level hierarchical (pods of clients)")
    ap.add_argument("--pods", type=int, default=2,
                    help="n_pods for --topology hier (clients split into "
                         "contiguous pods; 1 degenerates to the flat round)")
    ap.add_argument("--inter-reducer", default="int8",
                    help="inter-pod reducer for --topology hier "
                         "(the WAN hop): dense | int8 | int<b> | topk")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-out", default=None, metavar="DIR",
                    help="write the final params as a serveable checkpoint: "
                         "meta records arch/smoke + the full stagewise "
                         "schedule, so launch/serve.py --ckpt DIR can "
                         "rebuild the config and restore without flags")
    ap.add_argument("--trace", default=None, metavar="OUT.json",
                    help="export a Perfetto-loadable Chrome trace of the "
                         "run's span timeline (plus the comm.*/train.* "
                         "counter tracks) to this path, and a .jsonl span "
                         "log next to it")
    ap.add_argument("--profile", action="store_true",
                    help="wall-time the jitted train/sync steps (block-"
                         "until-ready) against their modeled prices and "
                         "print the modeled-vs-measured skew table")
    ap.add_argument("--profile-dir", default=None, metavar="DIR",
                    help="also bracket the run in a jax.profiler trace "
                         "session writing XPlane artifacts to DIR "
                         "(implies --profile)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = get_arch(args.arch, smoke=args.smoke, layers=args.layers)
    tcfg = TrainConfig(algo=args.algo, eta1=args.eta1, k1=args.k1, T1=args.T1,
                       n_stages=args.stages, iid=not args.non_iid,
                       gamma_inv=args.gamma_inv, momentum=args.momentum,
                       seed=args.seed, reducer=args.reducer,
                       topology=args.topology, n_pods=args.pods,
                       inter_reducer=args.inter_reducer)
    C = args.clients
    hier = args.topology == "hier"
    mesh = make_client_mesh(C, args.pods if hier else 1)
    client_axis = ("pod", "data") if hier else "data"

    log.info("arch=%s layers=%d algo=%s clients=%d mesh=%s", cfg.name,
             cfg.n_layers, args.algo, C, dict(mesh.shape))
    state = LS.init_sharded_state(jax.random.key(args.seed), cfg, C, mesh,
                                  args.optimizer, client_axis)
    batch_sh = {k: NamedSharding(mesh, spec) for k, spec in
                LS.batch_spec(cfg, client_axis, False).items()}
    # the round is the config's topology (the two-level round keeps its
    # --pods even where they share a chip), built once and run as is
    train_local, sync_step, _ = LS.build_train_steps(
        cfg, mesh, client_axis=client_axis, optimizer=args.optimizer,
        momentum=args.momentum, reducer=topology_for(tcfg))

    uses_center = args.algo in ("stl_nc1", "stl_nc2") and args.gamma_inv > 0
    if uses_center:
        from repro.core.prox import prox_loss

        pl = prox_loss(lambda p, b: LS.lm_loss(p, cfg, b), args.gamma_inv)

        def train_with_center(state, batch, eta, center):
            # rebuild a step closing over the center
            tl, _, _ = LS.build_train_steps(
                cfg, mesh, client_axis=client_axis,
                optimizer=args.optimizer,
                momentum=args.momentum,
                loss_fn=lambda p, c, b: pl(p, b, center))
            return tl(state, batch, eta)

        train_fn = jax.jit(train_with_center, donate_argnums=(0,))
    else:
        train_fn = jax.jit(train_local, donate_argnums=(0,))
    # the state is donated to every step: the driver only ever holds the
    # newest one, so one copy of it lives on the devices
    sync_fn = jax.jit(sync_step, donate_argnums=(0,))

    profile = None
    if args.profile or args.profile_dir:
        from repro.obs import ProfileSession
        from repro.serve.engine import DeviceModel

        profile = ProfileSession(logdir=args.profile_dir)
        # one train step = C clients × batch × seq tokens on the roofline
        train_price = DeviceModel().step_time_s(
            cfg, ShapeConfig("train_step", args.seq, C * args.batch,
                             "train"))
        # the sync round is priced from the driver's own topology, which
        # only exists below — resolve the price lazily per call
        sync_price = {"v": 0.0}
        train_fn = profile.wrap(train_fn, "train_step", train_price)
        # wrapping keeps the step's topology reachable through the
        # __wrapped__ chain, so the driver still prices the round it runs
        sync_fn = profile.wrap(sync_fn, "sync_step",
                               lambda *a, **k: sync_price["v"])

    driver = StagewiseDriver(tcfg, train_fn, sync_fn, uses_center=uses_center)
    if profile is not None:
        template = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape[1:], x.dtype),
            state["params"])
        sync_price["v"] = sum(
            h.time_s for h in driver.topology.hop_costs(template, C))
    batches = (jax.device_put(b, batch_sh) for b in synthetic_batches(
        cfg, C, args.batch, args.seq, args.seed, args.non_iid))
    tracer = None
    if args.trace:
        from repro.obs import Tracer
        from repro.utils.logging import RUN_ID
        tracer = Tracer(run_id=RUN_ID)
    t0 = time.time()
    if profile is not None:
        with profile:
            ds = driver.run(state, batches, max_iters=args.steps,
                            tracer=tracer)
    else:
        ds = driver.run(state, batches, max_iters=args.steps, tracer=tracer)
    dt = time.time() - t0
    log.info("done: %d iters, %d comm rounds, %.1fs (%.1f it/s)",
             ds.iters_total, ds.rounds_total, dt, ds.iters_total / max(dt, 1e-9))
    for r in ds.results:
        log.info("  stage %d: k=%d rounds=%d loss=%.4f", r.stage, r.k,
                 r.rounds, r.mean_loss)
        log.info("    losses=%s", [round(x, 4) for x in r.losses])
    if profile is not None:
        from repro.obs import format_skew_table
        profile.emit_spans(tracer)
        print(format_skew_table(profile.skew_table()))
    if tracer is not None:
        from repro.obs import series as obs_series
        from repro.obs import write_chrome_trace, write_jsonl
        write_chrome_trace(tracer, args.trace,
                           series=obs_series.registry())
        write_jsonl(tracer, args.trace + "l")   # foo.json -> foo.jsonl
        log.info("trace_written", path=args.trace, spans=len(tracer.spans))
    if args.ckpt_dir:
        save_checkpoint(args.ckpt_dir, ds.iters_total, ds.state["params"],
                        {"algo": args.algo, "rounds": ds.rounds_total})
        log.info("checkpoint written to %s", args.ckpt_dir)
    if args.ckpt_out:
        # serveable checkpoint: the consensus params x̄ (client-axis mean —
        # identical across clients right after a sync round), plus meta
        # carrying everything ServeEngine.from_checkpoint needs to rebuild
        # the arch and the stagewise schedule actually executed
        consensus = jax.tree.map(lambda p: p.mean(axis=0),
                                 ds.state["params"])
        meta = {
            "arch": args.arch, "smoke": bool(args.smoke),
            "layers": cfg.n_layers,
            "algo": args.algo, "eta1": args.eta1, "k1": args.k1,
            "T1": args.T1, "n_stages": args.stages,
            "iters": ds.iters_total, "rounds": ds.rounds_total,
            "stages": [{"stage": r.stage, "k": r.k, "rounds": r.rounds,
                        "eta": r.eta, "mean_loss": float(r.mean_loss)}
                       for r in ds.results],
        }
        path = save_checkpoint(args.ckpt_out, ds.iters_total, consensus,
                               meta)
        log.info("serveable checkpoint written to %s", path)
    return ds


if __name__ == "__main__":
    main()
