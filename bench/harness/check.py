"""The comparison that decides ``correct``.

Three numbers, each against its limit in ``bench/limits/<cell>.json``:

- ``loss_gap``: the widest relative gap, over the check steps, between the
  program's loss (mean over clients) and the reference's.
- ``grad_gap``: per leaf and client, the gap between the norm of the
  program's first gradient (its momentum after step 1, from zero) and the
  reference's, over the larger of that leaf's reference norm and the
  median leaf's; the worst leaf.
- ``change_gap``: the same measure on each leaf's change from the start
  after the last check step.

Leaves whose reference gradient is under a thousandth of the median
leaf's are left out of both norms' comparisons: they move by rounding
alone.
"""
from __future__ import annotations

import jax
import numpy as np

NAMES = ("loss_gap", "grad_gap", "change_gap")
NEGLIGIBLE = 1e-3


def _flat(tree) -> dict:
    """{(leaf path, client): value} of a tree of (C,) arrays."""
    out = {}
    for path, v in jax.tree_util.tree_flatten_with_path(tree)[0]:
        for c, x in enumerate(np.asarray(v, np.float64).reshape(-1)):
            out[(jax.tree_util.keystr(path), c)] = float(x)
    return out


def _worst_leaf(prog: dict, ref: dict, keep) -> float:
    med = float(np.median([ref[k] for k in keep]))
    return max(abs(prog[k] - ref[k]) / max(ref[k], med) for k in keep)


def gaps(prog: dict, ref: dict) -> dict:
    if len(prog["loss"]) != len(ref["loss"]):
        raise ValueError("program and reference ran different step counts")
    loss = max(abs(a - b) / abs(b) for a, b in zip(prog["loss"], ref["loss"]))
    rg, pg = _flat(ref["grad"]), _flat(prog["grad"])
    rc, pc = _flat(ref["change"]), _flat(prog["change"])
    if set(rg) != set(pg) or set(rc) != set(pc):
        raise ValueError("program and reference leaves differ")
    med = float(np.median(list(rg.values())))
    keep = [k for k in rg if rg[k] >= NEGLIGIBLE * med]
    return {"loss_gap": loss, "grad_gap": _worst_leaf(pg, rg, keep),
            "change_gap": _worst_leaf(pc, rc, keep),
            "leaves_left_out": len(rg) - len(keep)}


def worst_leaves(prog: dict, ref: dict, what: str, n: int = 3):
    """The ``n`` (leaf, client) pairs with the widest gap of ``what``
    ("grad" or "change"): [(leaf, client, program norm, reference norm)]."""
    p, r = _flat(prog[what]), _flat(ref[what])
    med = float(np.median(list(r.values())))
    order = sorted(r, key=lambda k: -abs(p[k] - r[k]) / max(r[k], med))
    return [(k[0], k[1], p[k], r[k]) for k in order[:n]]


def judge(numbers: dict, limits: dict):
    """(correct, [{"name", "value", "limit"}]) over the numbers the cell's
    limits name (a number without a limit is not compared); a number that
    is not finite fails."""
    rows = [{"name": n, "value": numbers[n], "limit": limits[n]}
            for n in NAMES if n in limits]
    ok = all(np.isfinite(r["value"]) and r["value"] <= r["limit"]
             for r in rows)
    return ok, rows
