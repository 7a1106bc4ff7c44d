"""Resolve a cell of ``BENCHMARK.json`` to the files that define it.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric lives in a file of its own, found by name:

    bench/configs/<config>.json     sizes, as run, and the source's
    bench/traffic/<traffic>.json    the training job: clients, lengths,
                                    schedule, round
    bench/metrics/<metric>.py       a reader: ``read(ctx) -> float | None``
    bench/limits/<cell>.json        the limit of each number compared
    bench/blocks/<block>.py         a layer kind's plain reference and its
                                    operation count, named by the config

A new cell, mix or metric is new files and new entries, never an edit.
"""
from __future__ import annotations

import importlib
import json
from dataclasses import dataclass, field
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent


def load_benchmark(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def _json(kind: str, name: str) -> dict:
    path = BENCH / kind / f"{name}.json"
    if not path.is_file():
        raise FileNotFoundError(f"no {kind} file for {name!r}: {path}")
    return json.loads(path.read_text())


def metric_reader(name: str):
    return importlib.import_module(f"bench.metrics.{name}")


def block_module(name: str):
    return importlib.import_module(f"bench.blocks.{name}")


@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    end_to_end: list = field(default_factory=list)
    per_layer: list = field(default_factory=list)


def _reports(metric: dict, cell: str, e2e_names=None) -> bool:
    """Whether ``cell`` reports ``metric``: the cells its ``workloads``
    lists, else every cell (end-to-end) or every cell that reports the
    end-to-end metric it ``moves`` (per-layer)."""
    if "workloads" in metric:
        return cell in metric["workloads"]
    return e2e_names is None or metric["moves"] in e2e_names


def resolve(cell_name: str, bench: dict | None = None) -> Cell:
    bench = bench or load_benchmark()
    cells = {w["name"]: w for w in bench["workloads"]}
    if cell_name not in cells:
        raise KeyError(f"unknown workload {cell_name!r}; "
                       f"BENCHMARK.json has {sorted(cells)}")
    w = cells[cell_name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = json.loads((ROOT / configs[w["config"]]["file"]).read_text())
    e2e = [m for m in bench["end_to_end"] if _reports(m, cell_name)]
    e2e_names = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if _reports(m, cell_name, e2e_names)]
    return Cell(name=cell_name, chips=int(w["chips"]), config=config,
                traffic=_json("traffic", w["traffic"]),
                limits=_json("limits", cell_name),
                end_to_end=e2e, per_layer=per_layer)
