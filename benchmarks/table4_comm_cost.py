"""Table 4 (beyond-paper): true communication cost of a round.

The paper only counts communication *rounds*; this table prices them. Each
cell runs one (algorithm × reducer) pair on the synthetic convex workload
and reports rounds, modeled bytes (repro.comm byte accounting for the
reducer's compressed representation), modeled wall-clock (α–β network
model) and the final objective — showing that stagewise periods (fewer
rounds) and compressed reducers (cheaper rounds) compose.

The claim under test: int8 / top-k reducers cut modeled bytes ≥ 3× while
landing within 5% of the dense final objective (error feedback absorbs the
compression bias).

A hierarchical pair of rows rides along (PR 5): the same stl_sc schedule
over 2 pods (dense intra-pod ICI + int8-EF inter-pod WAN), once through
the vmapped simulator and once through the ``StagewiseDriver`` — whose
sync step now emits the real two-level round — asserting the two
front-ends report identical rounds and bit-identical modeled bytes.

    PYTHONPATH=src python -m benchmarks.table4_comm_cost [--full]
"""
from __future__ import annotations

import itertools

import jax
import jax.numpy as jnp

from benchmarks.common import print_table, save_artifact, save_bench
from repro.comm import NetworkModel, comm_summary_for
from repro.configs.base import TrainConfig
from repro.core import local_sgd as LS
from repro.core import simulate
from repro.core.stl_sgd import StagewiseDriver, driver_state, \
    make_client_sgd_step
from repro.data import make_binary_classification, partition_iid
from repro.models import logreg

# "adaptive" is the divergence-triggered SyncPolicy (engine.AdaptivePeriod):
# stl_sc's η_s/T_s schedule, rounds fired by the replica-divergence probe
ALGOS = ("sync", "local", "stl_sc", "stl_nc1", "adaptive")
REDUCERS = ("dense", "int8", "topk")

# acceptance thresholds (also asserted by tests/test_comm.py)
MIN_BYTES_RATIO = 3.0
MAX_OBJ_DRIFT = 0.05


def make_problem(quick: bool, n_clients: int):
    n, d = (2048, 64) if quick else (16384, 123)
    x, y = make_binary_classification(n=n, d=d, seed=0)
    lam = 1e-3
    data = {k: jnp.asarray(v)
            for k, v in partition_iid(x, y, n_clients, seed=1).items()}
    xj, yj = jnp.asarray(x), jnp.asarray(y)
    loss_fn = lambda p, b: logreg.loss_fn(p, b, lam)
    eval_fn = jax.jit(lambda p: logreg.full_objective(p, xj, yj, lam))
    return loss_fn, eval_fn, logreg.init_params(None, d), data


def algo_cfg(algo: str, quick: bool, reducer: str) -> TrainConfig:
    T1 = 512 if quick else 2048
    kw = dict(eta1=0.5, iid=True, batch_per_client=32, seed=0,
              reducer=reducer, topk_frac=0.125)
    if algo == "sync":
        return TrainConfig(algo=algo, T1=T1 * 2, k1=1.0, n_stages=1, **kw)
    if algo == "local":
        return TrainConfig(algo=algo, T1=T1, k1=8.0, n_stages=2, **kw)
    if algo == "stl_nc1":
        return TrainConfig(algo=algo, T1=T1 // 4, k1=2.0, n_stages=6,
                           gamma_inv=0.1, **kw)
    return TrainConfig(algo=algo, T1=T1 // 4, k1=2.0, n_stages=6, **kw)


def run_hierarchical(loss_fn, eval_fn, p0, data, n_clients: int,
                     quick: bool):
    """The hierarchical column pair: simulator vs driver, same config.

    Returns two rows. Rounds must match (same stage stream) and modeled
    bytes must be bit-identical (both front-ends price the same
    ``engine.Hierarchical`` topology — the driver's the one its
    two-level sync step carries); asserted here so the bench doubles as the
    smoke test for the hierarchical driver path.
    """
    T1 = 512 if quick else 2048
    cfg = TrainConfig(algo="stl_sc", eta1=0.5, T1=T1 // 4, k1=2.0,
                      n_stages=6, iid=True, batch_per_client=32, seed=0,
                      topology="hier", reducer="dense", inter_reducer="int8",
                      n_pods=2)
    hist = simulate.run(loss_fn, p0, data, cfg, eval_fn, eval_every=64)
    summ = comm_summary_for(cfg, p0, n_clients, hist[-1].round)
    sim_row = {"algo": "stl_sc/hier", "reducer": summ["reducer"],
               "backend": "simulator", "rounds": hist[-1].round,
               "iters": hist[-1].iteration, "final_obj": hist[-1].value,
               "comm_bytes": summ["total_bytes"],
               "comm_time_s": summ["total_time_s"]}

    train_step = make_client_sgd_step(loss_fn, data, batch=32)
    sync_step = LS.build_sync_step("dense", hierarchical=True, n_pods=2,
                                   inter_reducer="int8")
    ds = StagewiseDriver(cfg, jax.jit(train_step), jax.jit(sync_step)).run(
        driver_state(p0, n_clients), itertools.repeat(None))
    obj = float(eval_fn(jax.tree.map(lambda x: x[0], ds.state["params"])))
    drv_row = {"algo": "stl_sc/hier", "reducer": summ["reducer"],
               "backend": "driver", "rounds": ds.rounds_total,
               "iters": ds.iters_total, "final_obj": obj,
               "comm_bytes": ds.comm_bytes_total,
               "comm_time_s": ds.comm_time_s}
    assert drv_row["rounds"] == sim_row["rounds"], (drv_row, sim_row)
    assert drv_row["comm_bytes"] == sim_row["comm_bytes"], (drv_row, sim_row)
    assert sum(l["bytes"] for l in ds.leaf_ledger) == ds.comm_bytes_total
    for r in (sim_row, drv_row):
        print(f"  {r['algo']:12s} {r['reducer']:10s} [{r['backend']:9s}] "
              f"rounds={r['rounds']:>6} bytes={r['comm_bytes']:.3e} "
              f"t={r['comm_time_s']:.2f}s obj={r['final_obj']:.6f}",
              flush=True)
    return [sim_row, drv_row]


def run(quick: bool = True):
    n_clients = 8 if quick else 32
    loss_fn, eval_fn, p0, data = make_problem(quick, n_clients)
    net = NetworkModel()
    rows = []
    for algo in ALGOS:
        base_obj = None
        base_bytes = None
        for red in REDUCERS:
            cfg = algo_cfg(algo, quick, red)
            hist = simulate.run(loss_fn, p0, data, cfg, eval_fn,
                                eval_every=64)
            summ = comm_summary_for(cfg, p0, n_clients, hist[-1].round)
            row = {"algo": algo, "reducer": summ["reducer"],
                   "rounds": hist[-1].round, "iters": hist[-1].iteration,
                   "final_obj": hist[-1].value,
                   "comm_bytes": summ["total_bytes"],
                   "comm_time_s": summ["total_time_s"]}
            if red == "dense":
                base_obj, base_bytes = row["final_obj"], row["comm_bytes"]
                row["bytes_x"], row["obj_drift"] = "1.0x", "0.0%"
            else:
                ratio = base_bytes / max(row["comm_bytes"], 1)
                drift = abs(row["final_obj"] - base_obj) / abs(base_obj)
                row["bytes_x"] = f"{ratio:.1f}x"
                row["obj_drift"] = f"{drift * 100:.2f}%"
                row["ok"] = (ratio >= MIN_BYTES_RATIO
                             and drift <= MAX_OBJ_DRIFT)
            print(f"  {algo:8s} {row['reducer']:8s} rounds={row['rounds']:>6} "
                  f"bytes={row['comm_bytes']:.3e} t={row['comm_time_s']:.2f}s "
                  f"obj={row['final_obj']:.6f} ({row['bytes_x']}, "
                  f"drift {row['obj_drift']})", flush=True)
            rows.append(row)
    rows.extend(run_hierarchical(loss_fn, eval_fn, p0, data, n_clients,
                                 quick))
    print_table("Table 4 — communication cost (rounds × bytes × modeled time)",
                rows, ["algo", "reducer", "backend", "rounds", "iters",
                       "final_obj", "comm_bytes", "comm_time_s", "bytes_x",
                       "obj_drift"])
    bad = [r for r in rows if r.get("ok") is False]
    assert not bad, f"compressed reducers missed the bytes/objective bar: {bad}"
    save_artifact("table4_comm_cost", rows)
    save_bench("table4_comm_cost", rows,
               meta={"network": {"latency_s": net.latency_s,
                                 "bandwidth_gbps": net.bandwidth_gbps},
                     "n_clients": n_clients,
                     "scale": "quick" if quick else "full"})
    return rows


if __name__ == "__main__":
    import sys

    run(quick="--full" not in sys.argv)
