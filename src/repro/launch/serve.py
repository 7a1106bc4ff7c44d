"""Serving launcher — continuous batching under open-loop synthetic load.

Drives ``repro.serve.ServeEngine``: restore a checkpoint (or init fresh
params), generate a Poisson/bursty request trace, run the
continuous-batching decode loop, and print the latency/throughput report
(modeled roofline numbers next to measured host wall-clock).

Examples:
  # serve a trained checkpoint (arch comes from checkpoint meta)
  PYTHONPATH=src python -m repro.launch.train --arch qwen3-14b --smoke \
      --steps 8 --ckpt-out /tmp/ck
  PYTHONPATH=src python -m repro.launch.serve --ckpt /tmp/ck \
      --process bursty --rate 500 --requests 32

  # or serve fresh random params by arch name
  PYTHONPATH=src python -m repro.launch.serve --arch qwen3-14b --smoke \
      --requests 16 --trace /tmp/serve_trace.json
"""
from __future__ import annotations

import argparse

import jax

from repro.configs import get_arch
from repro.launch.compile_cache import enable_compile_cache
from repro.models import transformer as TF
from repro.obs import write_chrome_trace, write_jsonl
from repro.obs.metrics import MetricsRegistry
from repro.obs.series import SeriesRegistry
from repro.serve import (
    SchedulerConfig,
    ServeEngine,
    TrafficConfig,
    arrival_summary,
    generate_requests,
)
from repro.utils.logging import get_logger

log = get_logger("serve")


def main(argv=None):
    ap = argparse.ArgumentParser()
    src = ap.add_mutually_exclusive_group(required=True)
    src.add_argument("--ckpt", metavar="DIR",
                     help="checkpoint dir from launch/train.py --ckpt-out "
                          "(arch is read from checkpoint meta)")
    src.add_argument("--arch", help="serve fresh random params for this arch")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--layers", type=int, default=None, metavar="N",
                    help="with --arch: cut the config to its first N "
                         "layers, widths unchanged (a checkpoint's cut "
                         "comes from its meta)")
    # traffic
    ap.add_argument("--process", default="poisson",
                    choices=["poisson", "bursty"])
    ap.add_argument("--rate", type=float, default=None, metavar="RPS",
                    help="offered arrival rate, modeled requests/s "
                         "(default: 0.7 × modeled capacity)")
    ap.add_argument("--requests", type=int, default=32)
    ap.add_argument("--prompt-len", type=int, default=16,
                    help="mean prompt length (geometric)")
    ap.add_argument("--gen", type=int, default=8,
                    help="mean output length (geometric)")
    ap.add_argument("--burst-factor", type=float, default=8.0)
    # scheduler
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-seq-len", type=int, default=128)
    ap.add_argument("--max-queue", type=int, default=64)
    ap.add_argument("--prefills-per-step", type=int, default=1)
    ap.add_argument("--trace", default=None, metavar="OUT.json",
                    help="export the request/decode span timeline plus the "
                         "serve.* counter tracks (queue depth, batch "
                         "occupancy, tokens/s) as a Perfetto-loadable "
                         "Chrome trace (+ .jsonl log)")
    ap.add_argument("--profile", action="store_true",
                    help="wall-time the jitted prefill/decode steps "
                         "(block-until-ready) against the roofline prices "
                         "and print the modeled-vs-measured skew table")
    ap.add_argument("--profile-dir", default=None, metavar="DIR",
                    help="also bracket the run in a jax.profiler trace "
                         "session writing XPlane artifacts to DIR "
                         "(implies --profile)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    enable_compile_cache()

    sched = SchedulerConfig(n_slots=args.slots, max_seq_len=args.max_seq_len,
                            max_queue=args.max_queue,
                            max_prefills_per_step=args.prefills_per_step)
    if args.ckpt:
        engine = ServeEngine.from_checkpoint(args.ckpt, scheduler=sched)
        cfg = engine.cfg
    else:
        cfg = get_arch(args.arch, smoke=args.smoke, layers=args.layers)
        params = TF.init_params(jax.random.key(args.seed), cfg)
        engine = ServeEngine(cfg, params, scheduler=sched)

    # default offered load: 70% of the modeled decode capacity, so the
    # out-of-the-box run sits below the knee of the latency curve
    capacity = sched.n_slots / engine.decode_step_s
    rate = args.rate if args.rate is not None else 0.7 * capacity
    mean_p, mean_g = args.prompt_len, args.gen
    tcfg = TrafficConfig(
        process=args.process, rate_rps=rate, n_requests=args.requests,
        mean_prompt_len=mean_p, max_prompt_len=min(4 * mean_p,
                                                   args.max_seq_len // 2),
        mean_out_len=mean_g, max_out_len=min(4 * mean_g,
                                             args.max_seq_len // 2),
        burst_factor=args.burst_factor, seed=args.seed)
    requests = generate_requests(tcfg, cfg.vocab_size)
    offered = arrival_summary(requests)
    log.info("arch=%s slots=%d capacity=%.0f tok/s offered=%.0f rps (%s)",
             cfg.name, sched.n_slots, capacity, offered["rate_rps"],
             args.process)

    tracer = None
    if args.trace:
        from repro.obs import Tracer
        from repro.utils.logging import RUN_ID
        tracer = Tracer(run_id=RUN_ID)
    registry = MetricsRegistry()
    series = SeriesRegistry()
    profile = None
    if args.profile or args.profile_dir:
        from repro.obs import ProfileSession
        profile = ProfileSession(logdir=args.profile_dir)
    if profile is not None:
        with profile:
            report = engine.run(requests, tracer=tracer, registry=registry,
                                series=series, profile=profile)
    else:
        report = engine.run(requests, tracer=tracer, registry=registry,
                            series=series)

    n_rej = len(report.rejected)
    log.info("served %d/%d requests (%d rejected), %d decode steps, "
             "mean occupancy %.2f/%d",
             len(report.completed), len(requests), n_rej, report.n_steps,
             report.mean_occupancy, sched.n_slots)
    log.info("modeled: makespan %.4fs, decode step %.2eS, %.0f tok/s | "
             "measured: %.2fs wall, %.0f tok/s",
             report.makespan_s, report.decode_step_s, report.modeled_tok_s,
             report.measured_wall_s, report.measured_tok_s)
    for name, s in report.latency_summary().items():
        log.info("  %-20s p50=%.2e p95=%.2e p99=%.2e (n=%d)", name,
                 s["p50"], s["p95"], s["p99"], s["count"])
    if profile is not None:
        from repro.obs import format_skew_table
        profile.emit_spans(tracer)
        print(format_skew_table(profile.skew_table()))
    if tracer is not None:
        write_chrome_trace(tracer, args.trace, series=series)
        write_jsonl(tracer, args.trace + "l")   # foo.json -> foo.jsonl
        log.info("trace_written", path=args.trace, spans=len(tracer.spans))
    return report


if __name__ == "__main__":
    main()
