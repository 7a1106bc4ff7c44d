"""Readings that the limits of a cell are set from, on the chip, in one process.

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,... \
        --control-seeds 1,2,3 --out <file.json>

For each of ``--seeds``: the program's set-up (one cycle from the seed,
as a run makes it) and the plain reference; the gaps between them are
the lower readings. For each of ``--control-seeds``: the control (the
reference in float8 matmuls) and the reference with each planted fault
(``half_batch``: the loss over half of each row's tokens; ``no_round``:
the averaging left out) put in the program's place, against the
reference; these are the upper readings. A step that returns its state
unchanged reads ``change_gap`` = 1 by the measure and needs no run.
Not part of a benchmark run; the limits in ``bench/limits`` are the ones
``limits`` gives from its readings (see ``PERF.md``).
"""
import time

T0 = time.time()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench.harness import check, cli, device, reference, spec  # noqa: E402


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--out", required=True)
    ap.add_argument("--limits-out", default=None,
                    help="write the limits the readings give to this file")
    args = ap.parse_args(argv)
    cell = spec.resolve(args.workload)
    devices = device.require_chips(cell.chips)
    import jax

    cli.use_compile_cache()
    from bench.harness.train import TrainCell

    tc = TrainCell(cell.config, cell.traffic, devices)
    ref = reference.Reference(cell.config, cell.traffic)
    out = {"cell": cell.name, "program": [], "control": [], "faults": []}

    def record(kind, row):
        out[kind].append(row)
        print(json.dumps({kind: row}), flush=True)
        Path(args.out).write_text(json.dumps(out, indent=1))

    refs = {}
    for seed in [int(s) for s in args.seeds.split(",") if s]:
        t = time.time()
        state, prog = tc.first_cycle(seed)
        jax.block_until_ready(state)
        cli._free(state)
        del state
        t_prog = time.time() - t
        t = time.time()
        r = refs[seed] = ref.run(seed, devices)
        row = dict(check.gaps(prog, r), seed=seed, prog_loss=prog["loss"],
                   worst_grad=check.worst_leaves(prog, r, "grad"),
                   worst_change=check.worst_leaves(prog, r, "change"),
                   ref_loss=r["loss"], prog_s=t_prog, ref_s=time.time() - t,
                   peak=device.memory_peak_bytes(devices))
        record("program", row)
    variants = {"control": reference.Reference(cell.config, cell.traffic,
                                               precision="fp8"),
                "half_batch": reference.Reference(cell.config, cell.traffic,
                                                  fault="half_batch"),
                "no_round": reference.Reference(cell.config, cell.traffic,
                                                fault="no_round")}
    for seed in [int(s) for s in args.control_seeds.split(",") if s]:
        r = refs.get(seed) or ref.run(seed, devices)
        for name, variant in variants.items():
            t = time.time()
            v = variant.run(seed, devices)
            row = dict(check.gaps(v, r), seed=seed, variant=name,
                       loss=v["loss"], s=time.time() - t)
            record("control" if name == "control" else "faults", row)
    out["limits"] = limits(out) if out["program"] else None
    out["total_s"] = time.time() - T0
    Path(args.out).write_text(json.dumps(out, indent=1))
    if args.limits_out and out["limits"]:
        Path(args.limits_out).write_text(json.dumps(out["limits"]["limits"]) + "\n")
    print(json.dumps({"limits": out["limits"], "total_s": out["total_s"]}))


def limits(out) -> dict:
    """Each number's limit from its readings. The lower reading is the
    largest of the program's; the upper is the least of the control's
    (where it is three times the lower or more) and each fault's (ten
    times, or three for a state left unchanged, which reads change_gap 1
    by the measure). The limit lies two thirds of the way from the lower
    to the upper on a log scale: room on both sides, more of it above the
    lower. A number with no upper reading is not compared."""
    chosen, readings = {}, {}
    for n in check.NAMES:
        lower = max(r[n] for r in out["program"])
        ups = [min(r[n] for r in out["control"])] if out["control"] else []
        ups = [u for u in ups if u >= 3 * lower]
        for variant in {r["variant"] for r in out["faults"]}:
            u = min(r[n] for r in out["faults"] if r["variant"] == variant)
            if u >= 10 * lower:
                ups.append(u)
        if n == "change_gap" and 1.0 >= 3 * lower:
            ups.append(1.0)
        readings[n] = {"lower": lower, "upper": min(ups) if ups else None}
        if ups:
            chosen[n] = float(f"{lower ** (1 / 3) * min(ups) ** (2 / 3):.2g}")
    return {"limits": chosen, "readings": readings}


if __name__ == "__main__":
    main()
