"""The rest of a run with the timed path broken underneath: ``correct`` has
to come out false, once for each fault a training cell can have. The fault is
planted in the program's step, beneath the harness's probe."""

import jax
import jax.numpy as jnp
import pytest

import tiny


def _plant(monkeypatch, breaker):
    from tensorflowdistributedlearning_tpu.train import step as step_lib

    real_factory = step_lib.make_train_step
    monkeypatch.setattr(
        step_lib, "make_train_step", lambda *a, **k: breaker(real_factory(*a, **k))
    )


def unchanged_state(real):
    def step(state, batch):
        kept = jax.tree.map(jnp.copy, state)  # the real step donates its input
        _, metrics = real(state, batch)
        return kept, metrics

    return step


def half_batch(real):
    def step(state, batch):
        first = jax.tree.map(lambda x: x[: x.shape[0] // 2], batch)
        # the other half never reaches the model; shapes stay what they were
        return real(state, jax.tree.map(lambda x: jnp.concatenate([x, x]), first))

    return step


def no_exchange(real, shards=4):
    def step(state, batch):
        # every chip works on chip 0's rows: what the update would be had the
        # gradients never been exchanged
        mine = jax.tree.map(lambda x: x[: x.shape[0] // shards], batch)
        return real(state, jax.tree.map(lambda x: jnp.concatenate([x] * shards), mine))

    return step


@pytest.mark.parametrize("breaker", [unchanged_state, half_batch])
def test_broken_step_is_not_correct(tmp_path, monkeypatch, breaker):
    _plant(monkeypatch, breaker)
    result, checks = tiny.run_cell(tmp_path, monkeypatch, "resnet50_fit")
    assert result["correct"] is False, checks


def test_unchanged_state_reads_one(tmp_path, monkeypatch):
    _plant(monkeypatch, unchanged_state)
    result, checks = tiny.run_cell(tmp_path, monkeypatch, "tgs_kfold_train")
    assert result["correct"] is False
    assert checks["delta_gap"][0] == pytest.approx(1.0)
    assert checks["grad1_gap"][0] == pytest.approx(1.0)


def test_left_out_exchange_is_not_correct(tmp_path, monkeypatch):
    _plant(monkeypatch, no_exchange)
    result, checks = tiny.run_cell(tmp_path, monkeypatch, "resnet50_fit_dp4", chips=4)
    assert result["correct"] is False, checks
