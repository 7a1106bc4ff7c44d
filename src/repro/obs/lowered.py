"""Counters that count a piece of a program each time it is lowered.

``lowering_counter(name, help=...)`` returns ``count(x, **labels)``: an
identity on ``x`` that adds one to ``name{labels}`` in
``repro.obs.metrics.registry()`` when the program holding it is lowered
to MLIR. It adds nothing to the lowered program. Inside a
``jax.lax.platform_dependent`` branch it counts only where that branch is
lowered. JAX lowers equal operations (same labels, same shapes) once per
program, so they count once there; a program served from the in-memory
jit cache is not lowered and counts nothing. Label values must be
hashable (they are primitive parameters).

The primitive is named after the counter with dots as underscores
(``attention.lowered`` stages as ``attention_lowered``), so a jaxpr shows
where each counter sits.
"""
from __future__ import annotations

from jax.extend.core import Primitive
from jax.interpreters import ad, batching, mlir

from repro.obs import metrics as obs_metrics


def lowering_counter(name: str, *, help: str, unit: str = "calls"):
    """An identity ``count(x, **labels)`` that counts ``name{labels}``
    once each time it is lowered."""
    prim = Primitive(name.replace(".", "_"))
    prim.def_impl(lambda x, **labels: x)
    prim.def_abstract_eval(lambda x, **labels: x)

    def count(x, **labels):
        return prim.bind(x, **labels)

    def lowering(ctx, x, **labels):
        obs_metrics.registry().counter(name, unit=unit, help=help).inc(
            **labels)
        return [x]

    mlir.register_lowering(prim, lowering)
    ad.primitive_jvps[prim] = (
        lambda primals, tangents, **labels:
        (count(primals[0], **labels), tangents[0]))
    batching.primitive_batchers[prim] = (
        lambda args, dims, **labels: (count(args[0], **labels), dims[0]))
    return count
