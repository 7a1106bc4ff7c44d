"""The system under test: stagewise Local SGD driven as the launcher drives it.

``repro.core.stl_sgd.StagewiseDriver.run`` over the local step and the
averaging round of ``repro.core.local_sgd.build_train_steps``, both jitted
with the state donated, on a ``(data, model)`` client mesh with the
state in ``local_sgd.state_shardings`` and batches in
``local_sgd.batch_spec`` (as ``repro/launch/train.py`` builds them). The
benchmark jits the two steps under names of its own, ``bench_local_step``
and ``bench_sync_round``, so that the trace reduction finds them whatever
the program calls its functions, and annotates each host call.

A cycle is one ``StagewiseDriver.run`` of the traffic's whole stage
schedule, continuing from the state the previous cycle left.
"""
from __future__ import annotations

import functools
import time

import jax
import jax.numpy as jnp
from jax.profiler import TraceAnnotation
from jax.sharding import NamedSharding, PartitionSpec as P

from bench.harness import data, yardstick
from bench.harness.reference import param_layout

def per_client_norms(tree):
    """Per leaf, the l2 norm of each client's replica: leaves of shape (C,)."""
    return jax.tree.map(
        lambda x: jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)),
                                   axis=tuple(range(1, x.ndim)))), tree)


class TrainCell:
    def __init__(self, config: dict, traffic: dict, devices):
        from repro.configs.base import TrainConfig
        from repro.core import local_sgd as LS
        from repro.core.stl_sgd import StagewiseDriver
        from repro.models import transformer as TF

        self.config, self.traffic = config, traffic
        self.arch = yardstick.arch_config(config)
        t = traffic
        self.C = t["clients"]
        self.tcfg = TrainConfig(
            algo=t["algo"], eta1=t["eta1"], k1=t["k1"], T1=t["T1"],
            n_stages=t["n_stages"], iid=t["iid"], momentum=t["momentum"],
            reducer=t["reducer"], topology=t["topology"],
            batch_per_client=t["batch_per_client"])
        auto = (jax.sharding.AxisType.Auto,) * 2
        self.mesh = jax.make_mesh((len(devices), 1), ("data", "model"),
                                  axis_types=auto, devices=devices)
        local, sync, _ = LS.build_train_steps(
            self.arch, self.mesh, client_axis="data", optimizer=t["optimizer"],
            momentum=t["momentum"], reducer=t["reducer"],
            streaming=t["topology"] == "streaming")

        def bench_local_step(state, batch, eta):
            return local(state, batch, eta)

        @functools.wraps(sync, assigned=("__module__", "__doc__"))
        def bench_sync_round(state):
            return sync(state)

        self.local_jit = jax.jit(bench_local_step, donate_argnums=(0,))
        self.round_jit = jax.jit(bench_sync_round, donate_argnums=(0,))
        self.on_step = None

        def local_call(state, batch, eta):
            with TraceAnnotation("bench.local_step"):
                out = self.local_jit(state, batch, eta)
            if self.on_step is not None:
                self.on_step(out[0])
            return out

        @functools.wraps(self.round_jit)
        def round_call(state):
            with TraceAnnotation("bench.sync_round"):
                return self.round_jit(state)

        self.driver = StagewiseDriver(self.tcfg, local_call, round_call)
        self.cycle_steps = sum(s.T for s in self.driver.stages)
        self.cycle_rounds = sum(-(-s.T // int(s.k)) for s in self.driver.stages)

        self.param_shapes = param_layout(config)
        if TF.init_params_shape(self.arch) != self.param_shapes:
            raise ValueError(f"the program lays out {config['name']!r}'s "
                             f"weights otherwise than bench/blocks do")
        shapes = LS.init_state_shape(self.arch, self.C, t["optimizer"])
        self.state_shapes = shapes
        self.state_sh = LS.state_shardings(self.arch, self.mesh,
                                           shapes["params"], shapes["opt"])
        self.batch_sh = {k: NamedSharding(self.mesh, s) for k, s in
                         LS.batch_spec(self.arch, "data", False).items()}
        C = self.C

        def make_state(p):
            stacked = jax.tree.map(
                lambda a: jnp.broadcast_to(a[None], (C,) + a.shape), p)
            return {"params": stacked,
                    "opt": {"mu": jax.tree.map(
                        lambda a: jnp.zeros(a.shape, jnp.float32), stacked)},
                    "step": jnp.zeros((), jnp.int32)}

        # one compiled initialiser: the weights the state starts from and
        # the ones a change is measured from are the same bits
        self.init = jax.jit(lambda key: data.init_params(key, self.param_shapes),
                            out_shardings=NamedSharding(self.mesh, P()))
        self.make_state = jax.jit(make_state, out_shardings=self.state_sh)
        self.norms = jax.jit(per_client_norms)
        self.change_norms = jax.jit(lambda params, p0: per_client_norms(
            jax.tree.map(lambda p, q: p.astype(jnp.float32)
                         - q.astype(jnp.float32)[None], params, p0)))

    # -- input ---------------------------------------------------------------

    def set_batches(self, seed: int):
        """The cycle's batches, all distinct, kept on the host."""
        self.host = data.host_batches(seed, self.traffic,
                                      self.config["vocab_size"],
                                      self.cycle_steps)

    def feed(self):
        """Device batches in a fixed order, from the first on each call."""
        while True:
            for hb in self.host:
                with TraceAnnotation("bench.fetch"):
                    b = jax.device_put(hb, self.batch_sh)
                yield b

    # -- the runs --------------------------------------------------------------

    def first_cycle(self, seed: int):
        """Set-up: the state from the seed, driven through one whole cycle
        by the window's own calls and feed, which warms every program and
        shape the window uses. Returns the state (handed on to the window)
        and what the check compares, from the traffic's first
        ``check_steps`` local steps: the loss of each, each leaf's first
        gradient as the optimizer holds it after step 1 (its momentum, from
        zero), and each leaf's change after the last check step, per
        client."""
        key = data.jax_key(seed)
        self.set_batches(seed)
        n = self.traffic["check_steps"]
        seen = {"calls": 0}

        def capture(state):
            seen["calls"] += 1
            if seen["calls"] == 1:
                seen["grad"] = jax.device_get(self.norms(state["opt"]["mu"]))
            if seen["calls"] == n:
                seen["change"] = jax.device_get(
                    self.change_norms(state["params"], self.init(key)))

        self.on_step = capture
        try:
            ds = self.driver.run(self.make_state(self.init(key)), self.feed())
        finally:
            self.on_step = None
        losses = [x for r in ds.results for x in r.losses][:n]
        return ds.state, {"loss": losses, "grad": seen["grad"],
                          "change": seen["change"]}

    def cycles(self, state, seconds: float):
        """Whole cycles back to back until ``seconds`` have passed."""
        n, losses = 0, []
        t0 = time.perf_counter()
        while True:
            with TraceAnnotation("bench.cycle"):
                ds = self.driver.run(state, self.feed())
            state = ds.state
            n += 1
            losses += [x for r in ds.results for x in r.losses]
            if time.perf_counter() - t0 >= seconds:
                break
        jax.block_until_ready(state)
        wall = time.perf_counter() - t0
        return state, {"cycles": n, "wall_s": wall, "losses": losses,
                       "steps": n * self.cycle_steps,
                       "rounds": n * self.cycle_rounds}

    def tokens_per_step(self) -> int:
        t = self.traffic
        return t["clients"] * t["batch_per_client"] * t["seq_len"]
