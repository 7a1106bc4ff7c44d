import os
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS", ""))

"""Multi-pod dry-run: prove every (arch × shape × mesh) lowers AND compiles.

For each cell we lower + compile the relevant step programs against
ShapeDtypeStruct inputs (zero allocation), print memory/cost analysis and
parse collective traffic per mesh axis, then write a JSON artifact consumed
by benchmarks/roofline.py and EXPERIMENTS.md.

Programs per cell:
  train_4k     → local_step   (Local SGD inner step: NO client-axis comm)
                 sync_step    (Alg.1 line 5: the parameter-averaging round)
                 syncsgd_step (baseline: grads all-reduced every step)
  prefill_32k  → prefill_step
  decode_32k / long_500k → serve_step (one token vs seq_len-sized cache)

Usage:
  python -m repro.launch.dryrun --arch qwen3-14b --shape train_4k [--multi-pod]
  python -m repro.launch.dryrun --all [--multi-pod]   # the full matrix
"""
import argparse
import json
import sys
import time
import traceback

import jax
import jax.numpy as jnp

from repro.configs import ARCHS, SHAPES
from repro.launch import hlo_analysis as H
from repro.launch.mesh import make_production_mesh
from repro.launch.specs import input_specs
from repro.core import local_sgd as LS
from repro.core import serving as SV


def mesh_shape_dict(mesh):
    return dict(zip(mesh.axis_names, mesh.devices.shape))


def _analyse(name, lowered, mesh, verbose=True):
    compiled = lowered.compile()
    txt = compiled.as_text()
    colls = H.parse_collectives_nested(txt, mesh_shape_dict(mesh))
    rec = {
        "program": name,
        "memory": H.memory_summary(compiled),
        "cost": H.cost_summary(compiled),  # NB: loop bodies counted once
        "collectives": H.collective_summary(colls),  # loop-weighted
    }
    if verbose:
        mem = rec["memory"]
        print(f"  [{name}] peak_bytes/device={mem.get('peak_bytes')} "
              f"flops={rec['cost'].get('flops'):.3e} "
              f"hbm_bytes={rec['cost'].get('bytes_accessed'):.3e} "
              f"coll_link_bytes={rec['collectives']['total_link_bytes']:.3e} "
              f"by_axes={rec['collectives']['by_axes']}")
    return rec


def dryrun_cell(arch: str, shape_name: str, mesh, *, verbose=True,
                hierarchical=False, microbatch=4, programs=None,
                overrides=None, donate=False):
    """Lower+compile all programs for one (arch, shape, mesh) cell."""
    t0 = time.time()
    kind, cfg, *rest = (lambda r: (r[0], r[1], *r[2:]))(  # unpack
        input_specs(arch, shape_name, mesh, overrides=overrides))
    records = []
    want = lambda p: programs is None or p in programs
    with jax.sharding.set_mesh(mesh):
        if kind == "train":
            state, batch, st_sh, b_sh, client_axis = rest
            if hierarchical and "pod" in mesh.axis_names:
                from repro.launch.specs import train_specs
                state, batch, st_sh, b_sh, client_axis = train_specs(
                    cfg, SHAPES[shape_name], mesh, client_axis="pod")
            local_step, sync_step, _ = LS.build_train_steps(
                cfg, mesh, client_axis=client_axis, microbatch=microbatch)
            if want("local_step"):
                jl = jax.jit(local_step, in_shardings=(st_sh, b_sh, None),
                             out_shardings=(st_sh, None),
                             donate_argnums=(0,) if donate else ())
                records.append(_analyse(
                    "local_step", jl.lower(state, batch, 0.1), mesh, verbose))

            if want("sync_step"):
                js = jax.jit(sync_step, in_shardings=(st_sh,), out_shardings=st_sh)
                records.append(_analyse(
                    "sync_step", js.lower(state), mesh, verbose))

            # multi-pod meshes additionally prove the two-level round
            # lowers: dense intra-pod (data axis) + int8-EF inter-pod
            # (pod axis) — the collectives engine.Hierarchical prices
            if (want("sync_step_2level") and "pod" in mesh.axis_names
                    and not hierarchical):
                n_pods = mesh_shape_dict(mesh)["pod"]
                s2 = LS.build_sync_step("dense", hierarchical=True,
                                        n_pods=n_pods, inter_reducer="int8")
                # EF residuals join the state on the first sync; shardings
                # for the new "comm" key follow the params replica layout
                j2 = jax.jit(s2, in_shardings=(st_sh,))
                records.append(_analyse(
                    "sync_step_2level", j2.lower(state), mesh, verbose))

            # SyncSGD baseline: same step + gradient all-reduce over clients
            if want("syncsgd_step"):
                syncsgd_step, _, _ = LS.build_train_steps(
                    cfg, mesh, client_axis=client_axis, microbatch=microbatch,
                    sync_grads=True)
                jss = jax.jit(syncsgd_step, in_shardings=(st_sh, b_sh, None),
                              out_shardings=(st_sh, None))
                records.append(_analyse(
                    "syncsgd_step", jss.lower(state, batch, 0.1), mesh, verbose))
        else:
            sp = rest[0]
            if SHAPES[shape_name].mode == "prefill":
                step = SV.build_prefill_step(cfg)
                args = [sp["params"], sp["cache"], sp["tokens"]]
                shs = [sp["params_sh"], sp["cache_sh"], sp["tokens_sh"]]
                if cfg.frontend:
                    args.append(sp["frontend"])
                    shs.append(sp["frontend_sh"])
                jp = jax.jit(step, in_shardings=tuple(shs),
                             out_shardings=(None, sp["cache_sh"]))
                records.append(_analyse(
                    "prefill_step", jp.lower(*args), mesh, verbose))
            else:
                step = SV.build_serve_step(cfg)
                jd = jax.jit(step,
                             in_shardings=(sp["params_sh"], sp["cache_sh"],
                                           sp["tokens_sh"]),
                             out_shardings=(None, sp["cache_sh"]),
                             donate_argnums=(1,) if donate else ())
                records.append(_analyse(
                    "serve_step",
                    jd.lower(sp["params"], sp["cache"], sp["tokens"]),
                    mesh, verbose))
    return {
        "arch": arch, "shape": shape_name,
        "mesh": mesh_shape_dict(mesh),
        "hierarchical": hierarchical,
        "arch_variant": cfg.name,
        "elapsed_s": round(time.time() - t0, 1),
        "programs": records,
    }


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--hierarchical", action="store_true",
                    help="pod-level clients (beyond-paper mode)")
    ap.add_argument("--out", default="artifacts/dryrun")
    ap.add_argument("--microbatch", type=int, default=4)
    ap.add_argument("--programs", default=None, help="comma-sep subset")
    ap.add_argument("--kv-int8", action="store_true", help="int8 KV cache variant")
    ap.add_argument("--donate", action="store_true",
                    help="donate state/cache buffers (in-place update)")
    args = ap.parse_args(argv)

    mesh = make_production_mesh(multi_pod=args.multi_pod)
    tag = "multipod" if args.multi_pod else "singlepod"
    os.makedirs(args.out, exist_ok=True)

    cells = []
    if args.all:
        for a in ARCHS:
            for s in SHAPES:
                cells.append((a, s))
    else:
        assert args.arch and args.shape, "--arch and --shape (or --all)"
        cells = [(args.arch, args.shape)]

    failures = []
    for arch, shape in cells:
        print(f"=== dryrun {arch} × {shape} × {tag} ===", flush=True)
        try:
            rec = dryrun_cell(arch, shape, mesh, hierarchical=args.hierarchical,
                              microbatch=args.microbatch,
                              programs=args.programs.split(',') if args.programs else None,
                              overrides={"kv_quant": True} if args.kv_int8 else None,
                              donate=args.donate)
            suffix = ("_hier" if args.hierarchical else "") + ("_kvint8" if args.kv_int8 else "") + ("_donate" if args.donate else "")
            fname = f"{args.out}/{arch}_{shape}_{tag}{suffix}.json"
            with open(fname, "w") as f:
                json.dump(rec, f, indent=1)
            print(f"  -> {fname} ({rec['elapsed_s']}s)", flush=True)
        except Exception as e:
            traceback.print_exc()
            failures.append((arch, shape, repr(e)))
    if failures:
        print("FAILURES:", failures)
        sys.exit(1)
    print("ALL CELLS OK")


if __name__ == "__main__":
    main()
