"""Production meshes (TPU v5e pods).

single-pod: (16, 16)   axes (data, model)   — 256 chips
multi-pod : (2, 16, 16) axes (pod, data, model) — 512 chips

Axis semantics (shared by sharding.rules and the engine topologies, see
docs/topologies.md):

  pod    inter-pod axis — one shard per pod, connected by the slow
         DCN/WAN links; the hop `engine.Hierarchical` compresses and the
         two-level sync round (`local_sgd.build_sync_step(
         hierarchical=True)`) crosses once per round.
  data   intra-pod client/batch axis — the paper's N clients live on the
         (pod × data) grid pod-major, so a leading client dim sharded
         ``P(("pod", "data"))`` puts each pod's clients on one contiguous
         slice and the intra-pod reduce on cheap ICI.
  model  tensor-parallel axis (heads / ffn / experts / vocab).

``make_production_mesh`` is a FUNCTION so importing this module never touches
jax device state; the dry-run sets XLA_FLAGS for 512 host devices before any
jax import, everything else sees the real 1-CPU topology.
"""
from __future__ import annotations

import math

import jax

_AUTO = jax.sharding.AxisType.Auto


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(_AUTO,) * len(axes))


def make_host_mesh(data: int = 1, model: int = 1):
    """Small mesh over whatever devices exist (tests / CPU runs)."""
    return jax.make_mesh((data, model), ("data", "model"),
                         axis_types=(_AUTO,) * 2)


def make_host_pod_mesh(pods: int = 2, data: int = 1, model: int = 1):
    """Small (pod, data, model) mesh for tests / CPU runs.

    The host-device miniature of the multi-pod production mesh: same axis
    names, so the two-level sync round and its HLO collective analysis run
    under ``--xla_force_host_platform_device_count`` exactly as they would
    on pods (requires ``pods * data * model`` host devices).
    """
    return jax.make_mesh((pods, data, model), ("pod", "data", "model"),
                         axis_types=(_AUTO,) * 3)


def make_client_mesh(n_clients: int, pods: int = 1):
    """The training mesh over every device present: client replicas spread
    over ``data`` (flat rounds, ``pods=1``) or over the ``(pod, data)``
    grid pod-major (two-level rounds), ``model`` of size 1.

    The pod axis takes ``gcd(pods, devices)`` chips, so one chip still
    runs a two-level round (its pods then share the chip). Each device
    holds ``n_clients / devices`` whole replicas; a client count the
    devices do not divide is refused rather than replicated.
    """
    n = jax.device_count()
    if n_clients % n:
        raise ValueError(f"{n_clients} clients cannot be split evenly over "
                         f"{n} devices")
    if pods == 1:
        return make_host_mesh(n, 1)
    p = math.gcd(pods, n)
    return make_host_pod_mesh(p, n // p, 1)


# v5e hardware constants for the roofline (per chip / per link). The α–β
# presets in comm/cost.py (``link_model("ici"/"dcn")``) are calibrated
# against ICI_BW / DCN_BW — converted to Gbit/s, with order-of-magnitude
# setup latencies — so modeled comm seconds in the benchmarks line up with
# the roofline's hardware model (units: B/s here, Gbit/s in NetworkModel;
# see docs/cost_model.md for the full units table).
PEAK_FLOPS_BF16 = 197e12   # FLOP/s
HBM_BW = 819e9             # B/s
ICI_BW = 50e9              # B/s per link
DCN_BW = 6.25e9            # B/s per host link (inter-pod data-center network)
