"""The trace reduction: interval arithmetic on made-up intervals, and the
whole reduction on a small trace recorded on a v5e chip by the harness's
``--trace 1`` path (a tiny MLA cell, one cycle of 2 local steps and 2
rounds under the harness's own names)."""
import gzip
from pathlib import Path

import pytest

from bench.harness import trace

SAMPLE = Path(__file__).resolve().parent / "data" / "sample.xplane.pb.gz"


def test_union_and_idle():
    iv = [(0, 10), (5, 12), (20, 30), (25, 26), (40, 50)]
    assert trace.union_length(iv) == 12 + 10 + 10
    assert trace.idle_intervals(iv, 0, 60) == [(12, 20), (30, 40), (50, 60)]
    assert trace.idle_intervals(iv, -5, 45) == [(-5, 0), (12, 20), (30, 40)]
    assert trace.union_length([]) == 0
    assert trace.idle_intervals([], 0, 5) == [(0, 5)]


def test_names():
    op = ("%all-reduce.3 = f32[]{:T(128)} all-reduce(%div.262), channel_id=1, "
          "to_apply=%region_35.36.clone")
    assert trace.op_kind(op) == "all-reduce" and trace.op_name(op) == "all-reduce.3"
    tup = ("%copy-start.4 = (bf16[2,1,2048,2560]{3,2,0,1:T(8,128)(2,1)}, u32[]{:S(2)}) "
           "copy-start(bf16[2,1,2048,2560]{3,2,0,1:T(8,128)(2,1)S(1)} %x)")
    assert trace.op_kind(tup) == "copy-start"
    assert trace.program_name("jit_bench_local_step(1264839661)") == "bench_local_step"
    spans = [("bench.traced", 0, 100), ("bench.cycle", 1, 99), ("bench.fetch", 10, 12)]
    assert trace.label_at(spans, 11) == "bench.fetch"
    assert trace.label_at(spans, 50) == "bench.cycle"
    assert trace.label_at(spans, 99.5) == "driver"


@pytest.fixture(scope="module")
def reduced():
    from jax.profiler import ProfileData

    xspace = gzip.decompress(SAMPLE.read_bytes())
    return trace.reduce(ProfileData.from_serialized_xspace(xspace))


def test_sample_programs(reduced):
    (dev,) = reduced["devices"]
    assert dev["programs"]["bench_local_step"]["calls"] == 2
    assert dev["programs"]["bench_sync_round"]["calls"] == 2
    assert dev["collective_s"] == 0


def test_sample_busy_and_gaps(reduced):
    assert 0 < reduced["busy_s"] < reduced["window_s"]
    (dev,) = reduced["devices"]
    programs_s = sum(p["device_s"] for p in dev["programs"].values())
    assert reduced["busy_s"] <= programs_s * 1.0001
    assert len(reduced["device_ops"]) == 10 and len(reduced["idle_gaps"]) == 10
    gaps = [s for _, s in reduced["idle_gaps"]]
    assert gaps == sorted(gaps, reverse=True)
    assert sum(gaps) <= reduced["window_s"] - reduced["busy_s"] + 1e-9
    assert all(n.startswith("bench.") or n == "driver" for n, _ in reduced["idle_gaps"])
