"""Dry-run integration on a small fake mesh (subprocess so XLA's device-count
flag doesn't leak into the main test process)."""
import json
import os
import subprocess
import sys

import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys; sys.path.insert(0, "@SRC@")
import jax, json, dataclasses
from repro.configs import get_arch, SHAPES
from repro.core import local_sgd as LS
from repro.launch import specs as SP
from repro.launch import hlo_analysis as H
from repro.launch.mesh import make_host_mesh

mesh = make_host_mesh(2, 4)
cfg = get_arch("@ARCH@", smoke=True)
shape = dataclasses.replace(SHAPES["train_4k"], seq_len=128, global_batch=8)
state, batch, st_sh, b_sh, ca = SP.train_specs(cfg, shape, mesh)
with jax.sharding.set_mesh(mesh):
    local_step, sync_step, _ = LS.build_train_steps(cfg, mesh, client_axis=ca,
                                                    microbatch=2)
    cl = jax.jit(local_step, in_shardings=(st_sh, b_sh, None),
                 out_shardings=(st_sh, None)).lower(state, batch, 0.1).compile()
    cs = jax.jit(sync_step, in_shardings=(st_sh,),
                 out_shardings=st_sh).lower(state).compile()
shape_d = dict(zip(mesh.axis_names, mesh.devices.shape))
loc = H.collective_summary(H.parse_collectives_nested(cl.as_text(), shape_d))
syn = H.collective_summary(H.parse_collectives_nested(cs.as_text(), shape_d))
print(json.dumps({"local": loc, "sync": syn}))
"""


@pytest.mark.parametrize("arch", ["qwen3-14b", "phi3.5-moe-42b-a6.6b",
                                  "mamba2-2.7b", "recurrentgemma-2b"])
def test_local_step_has_no_client_axis_traffic(arch):
    script = SCRIPT.replace("@SRC@", os.path.abspath(SRC)).replace("@ARCH@", arch)
    out = subprocess.run(
        [sys.executable, "-c", script],
        capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    # local step: data-axis traffic must be negligible — O(KB) control plane
    # (loss metrics; on MoE archs GSPMD also reshards the aux-loss scalars,
    # ~32KB) vs O(100MB+) parameter state moved by the sync round below.
    data_bytes = sum(v for k, v in res["local"]["by_axes"].items()
                     if "data" in k)
    assert data_bytes < 1e5, res["local"]
    # the averaging round must move real data over the client axis
    sync_data = sum(v for k, v in res["sync"]["by_axes"].items()
                    if "data" in k)
    assert sync_data > 1e5, res["sync"]
