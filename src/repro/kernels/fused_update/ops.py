"""Jit'd wrapper for the fused update kernel + pytree-level API.

``impl`` is required: "pallas" (TPU) | "interpret" (CPU validation) |
"xla" (oracle).
"""
from __future__ import annotations

import jax

from repro.kernels.fused_update import ref as R
from repro.kernels.fused_update.kernel import fused_sgd_update


def sgd_update(p, m, g, *, eta: float, beta: float = 0.0, wd: float = 0.0,
               impl: str):
    """Single-leaf fused momentum-SGD update."""
    if impl == "xla":
        return R.sgd_update_ref(p, m, g, eta=eta, beta=beta, wd=wd)
    return fused_sgd_update(p, m, g, eta=eta, beta=beta, wd=wd,
                            interpret=impl == "interpret")


def tree_sgd_update(params, moments, grads, *, eta, beta=0.0, wd=0.0,
                    impl: str):
    """Fused update over a whole parameter pytree."""
    flat_p, treedef = jax.tree.flatten(params)
    flat_m = treedef.flatten_up_to(moments)
    flat_g = treedef.flatten_up_to(grads)
    out_p, out_m = [], []
    for p, m, g in zip(flat_p, flat_m, flat_g):
        p2, m2 = sgd_update(p, m, g, eta=eta, beta=beta, wd=wd, impl=impl)
        out_p.append(p2)
        out_m.append(m2)
    return treedef.unflatten(out_p), treedef.unflatten(out_m)
