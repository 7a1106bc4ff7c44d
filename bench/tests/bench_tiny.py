"""Tiny cells for the benchmark's CPU tests: the harness's own code paths
at a size a test run holds."""
import contextlib
import io
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench.harness import cli, spec  # noqa: E402
import repro.core.stl_sgd  # noqa: E402,F401  (its logger binds stdout now)

DATA = Path(__file__).resolve().parent / "data"
# set from tiny runs on the CPU, seeds 1-8: sound runs read at most
# 6.2e-5, 0.0048 and 0.058; the float8 control at least 7.4e-5, 0.0185
# and 0.021 (grad_gap is the number that separates them here)
LIMITS = {"loss_gap": 1.5e-4, "grad_gap": 0.008, "change_gap": 0.1}
PEAKS = json.loads((ROOT / "bench" / "peaks.json").read_text())["TPU v5 lite"]


def load(name):
    return json.loads((DATA / f"{name}.json").read_text())


def tiny_cell(config="tiny-mla", per_layer=()):
    return spec.Cell(name="tiny", chips=1, config=load(config),
                     traffic=load("tiny-traffic"), limits=dict(LIMITS),
                     end_to_end=[], per_layer=list(per_layer))


def run(cell, seed=7, trace=0):
    """One run of ``cell`` on the CPU through the harness; its result."""
    import jax

    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        rc = cli.main(["--workload", cell.name, "--seed", str(seed),
                       "--seconds", "0.5", "--trace", str(trace)],
                      t0=time.time(), cell=cell, devices=jax.devices()[:1],
                      peaks=PEAKS)
    assert rc == 0
    return json.loads(out.getvalue().strip().splitlines()[-1])
