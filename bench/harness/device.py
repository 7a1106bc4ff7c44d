"""The accelerator a run measures, its published peaks, its memory peak.

A run needs a TPU whose ``device_kind`` is in ``bench/peaks.json`` and at
least as many chips as the cell asks for. Anything else is an error: the
benchmark has no CPU fallback, and a device without peaks has no
roofline.
"""
from __future__ import annotations

import json

from bench.harness.spec import BENCH


class NoChip(RuntimeError):
    pass


def peaks_table() -> dict:
    return json.loads((BENCH / "peaks.json").read_text())


def peaks_for(kind: str) -> dict:
    table = peaks_table()
    if kind not in table:
        raise NoChip(f"no published peaks for device kind {kind!r}; "
                     f"bench/peaks.json has {sorted(table)}")
    return table[kind]


def require_chips(chips: int):
    """The first ``chips`` accelerator devices, or ``NoChip``."""
    import jax

    devices = jax.devices()
    if not devices or devices[0].platform != "tpu":
        found = devices[0].platform if devices else "nothing"
        raise NoChip(f"needs a TPU; JAX found {found!r}")
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} chips; JAX found "
                     f"{len(devices)}")
    peaks_for(devices[0].device_kind)
    return devices[:chips]


def memory_peak_bytes(devices) -> int:
    """Peak bytes in use on the fullest of ``devices`` so far (0 where the
    backend keeps no count, as the CPU's)."""
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)


def describe(devices) -> dict:
    d = devices[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices)}
