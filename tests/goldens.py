"""Golden (round, iteration, objective) traces shared by the bit-exact tests.

``tests/test_engine.py`` pins the vmapped simulator and
``tests/test_runtime.py`` the event runtime to these tables: with
heterogeneity off, both backends must land on them bit for bit.

Recorded with jax/jaxlib 0.9.0 on the CPU, ``jax_threefry_partitionable``
True (jax's default since 0.5.0). The tables first recorded for these
tests came from an older jax whose default was False: the minibatch
indices drawn from the same keys differ under the new threefry stream,
so those trajectories moved by up to 0.6% while every round/iteration
column stayed the same. Under ``JAX_THREEFRY_PARTITIONABLE=0`` the
program still reproduces the old tables to 6.0e-7 relative, a few float32
ulps (the first row, before any minibatch, moved by as much).

Regenerate (and compare against the tables below) with

    PYTHONPATH=src JAX_PLATFORMS=cpu python tests/goldens.py
"""
import jax.numpy as jnp

from repro.configs.base import TrainConfig
from repro.data import make_binary_classification, partition_iid
from repro.models import logreg


def golden_problem():
    """(loss_fn, eval_fn, p0, client data): l2 logistic regression, 512
    samples of 16 features split IID over 4 clients."""
    x, y = make_binary_classification(n=512, d=16, seed=3)
    lam = 1e-2
    data = {k: jnp.asarray(v)
            for k, v in partition_iid(x, y, 4, seed=0).items()}
    xj, yj = jnp.asarray(x), jnp.asarray(y)
    loss_fn = lambda p, b: logreg.loss_fn(p, b, lam)
    eval_fn = lambda p: logreg.full_objective(p, xj, yj, lam)
    return loss_fn, eval_fn, logreg.init_params(None, 16), data


# stl_sc + DenseMean, seed 0, eval every round
STL_SC_CFG = dict(algo="stl_sc", eta1=0.5, T1=16, k1=2.0, n_stages=4,
                  iid=True, batch_per_client=8, seed=0)
STL_SC_RUN = dict(eval_every=1)

# stl_sc Non-IID, momentum=0.9, lr_alpha=1e-3, chunk_rounds=4 (exercises
# chunk boundaries, eval_every>1 and the k=√2 growth floor)
STL_SC_MOM_CFG = dict(algo="stl_sc", eta1=0.3, T1=12, k1=3.0, n_stages=3,
                      iid=False, batch_per_client=8, momentum=0.9, seed=7)
STL_SC_MOM_RUN = dict(eval_every=2, lr_alpha=1e-3, chunk_rounds=4)

GOLDEN_STL_SC = [
    (0, 0, 0.693147599697113), (1, 2, 0.6815382838249207),
    (2, 4, 0.6680716276168823), (3, 6, 0.6574758887290955),
    (4, 8, 0.6477485299110413), (5, 10, 0.6409918665885925),
    (6, 12, 0.6320326924324036), (7, 14, 0.625556468963623),
    (8, 16, 0.6203300952911377), (9, 20, 0.6133349537849426),
    (10, 24, 0.6071576476097107), (11, 28, 0.6011523604393005),
    (12, 32, 0.5952181816101074), (13, 36, 0.5893480777740479),
    (14, 40, 0.5848692655563354), (15, 44, 0.5805741548538208),
    (16, 48, 0.5766708254814148), (17, 56, 0.572992205619812),
    (18, 64, 0.5697740912437439), (19, 72, 0.5661476254463196),
    (20, 80, 0.563302755355835), (21, 88, 0.5603793263435364),
    (22, 96, 0.557345449924469), (23, 104, 0.554656445980072),
    (24, 112, 0.5521746873855591), (25, 128, 0.5495442748069763),
    (26, 144, 0.5467582941055298), (27, 160, 0.5445052981376648),
    (28, 176, 0.5422866344451904), (29, 192, 0.540480375289917),
    (30, 208, 0.5386694073677063), (31, 224, 0.5368478894233704),
    (32, 240, 0.5352817177772522),
]

GOLDEN_STL_SC_MOM = [
    (0, 0, 0.693147599697113), (2, 6, 0.6424731016159058),
    (4, 12, 0.5704336762428284), (6, 20, 0.5377852320671082),
    (8, 28, 0.5194005966186523), (10, 36, 0.5100637674331665),
    (12, 48, 0.506859302520752), (14, 60, 0.5050371289253235),
    (16, 72, 0.5039403438568115), (18, 84, 0.5036699175834656),
]


def trace(history):
    return [(h.round, h.iteration, float(h.value)) for h in history]


def generate():
    """The tables above, recomputed by ``simulate.run``."""
    from repro.core import simulate

    loss_fn, eval_fn, p0, data = golden_problem()
    return {name: trace(simulate.run(loss_fn, p0, data, TrainConfig(**cfg),
                                     eval_fn, **run))
            for name, cfg, run in (
                ("GOLDEN_STL_SC", STL_SC_CFG, STL_SC_RUN),
                ("GOLDEN_STL_SC_MOM", STL_SC_MOM_CFG, STL_SC_MOM_RUN))}


if __name__ == "__main__":
    for name, rows in generate().items():
        print(f"{name} = {rows!r}")
        print(f"  matches the table: {rows == globals()[name]}")
