"""The launchers on real devices, at smoke widths on the CPU.

* ``launch.train.main``: ``--layers`` depth cut, state placed on the
  device mesh and donated to the steps, ``--ckpt-out`` meta;
* ``ServeEngine.from_checkpoint`` rebuilds the depth cut from meta, and
  ``launch.serve --arch --layers`` serves a cut config;
* ``StagewiseDriver`` never touches a donated state;
* the compile-cache directory rule;
* ``chip_smoke.py`` refuses to run without a TPU.
"""
import itertools
import os
import shutil
import subprocess
import sys
from pathlib import Path

import jax
import numpy as np
import pytest
from jax.sharding import NamedSharding

from repro.configs import TrainConfig, get_arch
from repro.core import local_sgd as LS
from repro.core.stl_sgd import StagewiseDriver
from repro.launch import serve as serve_cli
from repro.launch import train as train_cli
from repro.launch.compile_cache import REPO_CACHE_DIR, enable_compile_cache
from repro.launch.mesh import make_client_mesh
from repro.obs import WALL, read_jsonl
from repro.serve import SchedulerConfig, ServeEngine

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture
def cache_dir_restored(monkeypatch, tmp_path):
    """Launchers point the compile cache somewhere: keep it off the repo
    during tests and restore the process's setting afterwards."""
    was = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jc"))
    yield tmp_path / "jc"
    jax.config.update("jax_compilation_cache_dir", was)


def test_get_arch_layers_cuts_depth_only():
    full = get_arch("minicpm3-4b")
    cut = get_arch("minicpm3-4b", layers=4)
    assert cut.n_layers == 4
    assert cut.replace(n_layers=full.n_layers) == full
    for bad in (0, full.n_layers + 1):
        with pytest.raises(ValueError):
            get_arch("minicpm3-4b", layers=bad)


def test_train_main_layers_placement_and_ckpt_meta(tmp_path,
                                                   cache_dir_restored):
    ckpt = tmp_path / "ck"
    ds = train_cli.main([
        "--arch", "qwen3-14b", "--smoke", "--layers", "1",
        "--clients", "2", "--batch", "1", "--seq", "16", "--momentum",
        "0.9", "--steps", "4", "--k1", "2", "--T1", "2", "--stages", "2",
        "--ckpt-out", str(ckpt), "--trace", str(tmp_path / "t.json")])
    assert jax.config.jax_compilation_cache_dir == str(cache_dir_restored)
    assert [r.k for r in ds.results] == [2, 4]
    assert ds.iters_total == 4
    spans = [s for s in read_jsonl(str(tmp_path / "t.jsonl"))
             if s.clock == WALL]
    stages = [s for s in spans if s.name == "stage"]
    assert len(stages) == len(ds.results)
    for st, r in zip(stages, ds.results):
        inside = [s for s in spans if st.t0 <= s.t0 and s.t1 <= st.t1]
        steps = [s for s in inside if s.name == "step"]
        assert len(r.losses) == len(steps) == r.iters
        assert len([s for s in inside if s.name == "reduce"]) == r.rounds
        assert all(np.isfinite(r.losses))
        assert min(s.duration for s in steps) > 0
    # one layer at smoke widths, replicas on the device mesh's data axis
    cfg = get_arch("qwen3-14b", smoke=True, layers=1)
    want = LS.init_state_shape(cfg, 2)
    for got, w in zip(jax.tree.leaves(ds.state["params"]),
                      jax.tree.leaves(want["params"])):
        assert got.shape == w.shape
        assert isinstance(got.sharding, NamedSharding)
        assert got.sharding.mesh.axis_names == ("data", "model")
    eng = ServeEngine.from_checkpoint(
        str(ckpt), scheduler=SchedulerConfig(n_slots=2, max_seq_len=32))
    assert eng.cfg == cfg


def test_from_checkpoint_rebuilds_depth_cut(tmp_path):
    from repro.checkpoint import save_checkpoint
    from repro.models import transformer as TF

    cfg = get_arch("minicpm3-4b", smoke=True, layers=1)
    params = TF.init_params(jax.random.key(0), cfg)
    save_checkpoint(str(tmp_path), 3, params,
                    {"arch": "minicpm3-4b", "smoke": True, "layers": 1})
    eng = ServeEngine.from_checkpoint(
        str(tmp_path), scheduler=SchedulerConfig(n_slots=2, max_seq_len=32))
    assert eng.cfg.n_layers == 1 and eng.cfg == cfg
    for a, b in zip(jax.tree.leaves(eng.params), jax.tree.leaves(params)):
        assert np.array_equal(np.asarray(a), np.asarray(b))


def test_serve_main_arch_layers(cache_dir_restored):
    report = serve_cli.main(["--arch", "minicpm3-4b", "--smoke",
                             "--layers", "1", "--requests", "3",
                             "--slots", "2", "--max-seq-len", "64"])
    assert len(report.completed) == 3 and not report.rejected
    with pytest.raises(ValueError):
        serve_cli.main(["--arch", "minicpm3-4b", "--smoke", "--layers", "9",
                        "--requests", "1"])


def test_driver_runs_on_donated_state():
    """Every step donates its state: the driver must only ever hand the
    newest state on, or a step would read a deleted buffer."""
    cfg = get_arch("qwen3-14b", smoke=True, layers=1)
    mesh = make_client_mesh(2)
    state = LS.init_sharded_state(jax.random.key(0), cfg, 2, mesh)
    first = jax.tree.leaves(state)
    train_local, sync, _ = LS.build_train_steps(cfg, mesh, momentum=0.9,
                                                reducer="int8")
    drv = StagewiseDriver(
        TrainConfig(algo="stl_sc", eta1=0.01, k1=2, T1=2, n_stages=2),
        jax.jit(train_local, donate_argnums=(0,)),
        jax.jit(sync, donate_argnums=(0,)))
    batch = next(train_cli.synthetic_batches(cfg, 2, 1, 8))
    ds = drv.run(state, itertools.repeat(batch))
    assert all(x.is_deleted() for x in first)
    assert not any(x.is_deleted() for x in jax.tree.leaves(ds.state))
    assert ds.rounds_total == 2 and "comm" in ds.state
    assert all(np.isfinite(r.mean_loss) for r in ds.results)


def test_compile_cache_dir_rule(monkeypatch):
    was = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        assert enable_compile_cache() == str(ROOT / ".jax_cache")
        assert REPO_CACHE_DIR == ROOT / ".jax_cache"
        assert jax.config.jax_compilation_cache_dir == str(REPO_CACHE_DIR)
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/where")
        assert enable_compile_cache() == "/some/where"
        assert jax.config.jax_compilation_cache_dir == "/some/where"
    finally:
        jax.config.update("jax_compilation_cache_dir", was)


def test_compile_cache_lands_in_env_dir(tmp_path):
    """In a fresh process (the cache is decided at the first compile),
    a compile is written where ``JAX_COMPILATION_CACHE_DIR`` says."""
    code = ("import jax, jax.numpy as jnp\n"
            "from repro.launch.compile_cache import enable_compile_cache\n"
            "print(enable_compile_cache())\n"
            "jax.config.update('jax_persistent_cache_min_compile_time_secs',"
            " 0)\n"
            "jax.jit(lambda x: x * 2 + 1)(jnp.ones(3)).block_until_ready()\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path),
               PYTHONPATH=str(ROOT / "src"))
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.strip() == str(tmp_path)
    assert any(tmp_path.iterdir())


@pytest.mark.parametrize("alone", [False, True],
                         ids=["cpu_only", "without_repo"])
def test_chip_smoke_refuses_without_tpu(tmp_path, alone):
    script = ROOT / "chip_smoke.py"
    if alone:
        script = Path(shutil.copy(script, tmp_path / "chip_smoke.py"))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    out = subprocess.run([sys.executable, str(script)], env=env,
                         cwd=tmp_path, capture_output=True, text=True,
                         timeout=300)
    assert out.returncode != 0
    assert '"ok"' not in out.stdout
    if not alone:
        assert "needs a TPU" in out.stderr
