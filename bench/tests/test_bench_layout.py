"""Every entry of BENCHMARK.json resolves to its files, and the
yardstick's numbers for the configurations are the ones the cells rest on."""
import json
import re

import jax
import pytest

from bench_tiny import ROOT, load
from bench.harness import reference, spec, yardstick

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
CELLS = [w["name"] for w in BENCH["workloads"]]


def test_benchmark_keys_and_names():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    names = ([c["name"] for c in BENCH["configs"]] + CELLS
             + [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]])
    assert all(NAME.match(n) for n in names)
    assert len(set(names)) == len(names)
    assert {m["name"] for m in BENCH["end_to_end"]} >= {"setup_s"}
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25 and m["source"] in ("host_clock", "device_trace")
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e and set(m["workloads"]) <= set(CELLS)


@pytest.mark.parametrize("cell", CELLS)
def test_cell_resolves_to_its_files(cell):
    c = spec.resolve(cell, BENCH)
    assert c.chips in (1, 4) and c.end_to_end and c.per_layer
    assert c.limits and set(c.limits) <= {"loss_gap", "grad_gap", "change_gap"}
    for m in c.per_layer:
        assert callable(spec.metric_reader(m["name"]).read)
    for kind in set(c.config["block_pattern"]):
        for b in yardstick.blocks_of(c.config, kind):
            assert callable(b.apply) and callable(b.param_shapes)


@pytest.mark.parametrize("entry", BENCH["configs"], ids=lambda e: e["name"])
def test_config_file_and_layout(entry):
    """The file holds the configuration as run; the program lays out its
    weights as the benchmark's blocks say."""
    from repro.models import transformer as TF

    cfg = json.loads((ROOT / entry["file"]).read_text())
    assert cfg["name"] == entry["name"] and cfg["reduced"] == entry["reduced"]
    assert set(cfg["published"]) == set(cfg["reduced"])
    arch = yardstick.arch_config(cfg)
    assert TF.init_params_shape(arch) == reference.param_layout(cfg)


def _cell(name):
    return spec.resolve(name, BENCH)


def test_minicpm3_numbers():
    """438,790,656 parameters as the program holds them, 10,530,975,744
    least bytes per one-chip round and 1.143e13 model FLOPs per step."""
    c = _cell("minicpm3-4b-l4.stl2x2048")
    layout = reference.param_layout(c.config)
    assert sum(x.size for x in jax.tree.leaves(layout)) == 438_790_656
    from repro.core import local_sgd as LS

    shapes = LS.init_state_shape(yardstick.arch_config(c.config), 2)
    assert yardstick.round_least_bytes(shapes, 2) == 10_530_975_744
    assert yardstick.step_flops(c.config, c.traffic) == pytest.approx(1.143e13, rel=5e-4)


def test_mamba2_numbers():
    """The SSD configuration kept for the cell the program's fault holds
    back (PERF.md): 450,797,952 parameters, 1.146e13 FLOPs per step, and
    the program lays out its weights as the benchmark's blocks say."""
    from repro.models import transformer as TF

    cfg = json.loads((ROOT / "bench/configs/mamba2-2.7b-l8.json").read_text())
    traffic = json.loads((ROOT / "bench/traffic/stl2x2048.json").read_text())
    layout = reference.param_layout(cfg)
    assert sum(x.size for x in jax.tree.leaves(layout)) == 450_797_952
    assert yardstick.step_flops(cfg, traffic) == pytest.approx(1.146e13, rel=5e-4)
    assert TF.init_params_shape(yardstick.arch_config(cfg)) == layout


def test_block_flops_by_hand():
    """Causal pairs and chunked SSD terms, against numbers worked by hand."""
    mla = load("tiny-mla")
    # 2 heads x (64*65/2 pairs) x (16+8+16) x 2
    assert yardstick.mixer_flops(mla, 64) == 2 * (2 * 2080 * 40 * 2)
    ssd = load("tiny-ssd")
    # H 8, P 16, N 16, Q 32, two chunks; conv 64 x (128+32) x 4 taps
    per_chunk = 528 * 16 + 8 * 528 * 16 + 2 * (8 * 32 * 16 * 16) + 8 * 16 * 16
    expect = 2 * (2.0 * (2 * per_chunk) + 2.0 * 64 * 160 * 4)
    assert yardstick.mixer_flops(ssd, 64) == pytest.approx(expect)
