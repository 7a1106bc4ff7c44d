"""The check fails a broken timed path: each fault a training cell can
have, planted in the program underneath a whole run on the CPU."""
import jax
import jax.numpy as jnp
import pytest

from bench_tiny import run, tiny_cell


def _unchanged_state(monkeypatch):
    """A step that returns its state unchanged (the update does nothing)."""
    from repro.core import local_sgd as LS

    make = LS.make_optimizer

    def broken(*a, **k):
        init, _ = make(*a, **k)
        return init, lambda params, grads, state, eta: (params, state)

    monkeypatch.setattr(LS, "make_optimizer", broken)


def _half_batch(monkeypatch):
    """Half of each row's tokens left out, the mean over the rest."""
    from repro.core import local_sgd as LS

    lm_loss = LS.lm_loss

    def broken(params, cfg, batch):
        S = batch["tokens"].shape[1] // 2
        return lm_loss(params, cfg, {k: v[:, :S] for k, v in batch.items()})

    monkeypatch.setattr(LS, "lm_loss", broken)


def _no_round(monkeypatch):
    """The exchange between clients left out: the round returns its input."""
    from repro.core import local_sgd as LS

    build = LS.build_sync_step

    def broken(*a, **k):
        real = build(*a, **k)

        def sync_step(state):
            return jax.tree.map(jnp.copy, state)

        sync_step.__dict__.update(real.__dict__)
        return sync_step

    monkeypatch.setattr(LS, "build_sync_step", broken)


FAULTS = {"unchanged_state": _unchanged_state, "half_batch": _half_batch,
          "no_round": _no_round}


@pytest.mark.parametrize("config", ["tiny-mla", "tiny-ssd"])
def test_sound_run_is_correct(config):
    res = run(tiny_cell(config))
    assert res["correct"], res["checks"]
    assert list(res)[-1] == "checks"
    assert set(res["metrics"]) == {"train_tokens_per_s", "setup_s"}


@pytest.mark.parametrize("fault", sorted(FAULTS))
@pytest.mark.parametrize("config", ["tiny-mla", "tiny-ssd"])
def test_fault_is_caught(config, fault, monkeypatch):
    FAULTS[fault](monkeypatch)
    res = run(tiny_cell(config))
    assert not res["correct"], res["checks"]
