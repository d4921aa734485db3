"""A run with its timed path broken underneath comes out not correct:
the harness's look for a chip is skipped, everything else of a run is
driven at smoke size on the CPU.  Faults a one-chip cell can have: a
token altered where it is produced, a step that returns its state
unchanged, half of the batch left out."""
import jax
import jax.numpy as jnp
import pytest

import harness
import smoke

SERVE_CELLS = [w["name"] for w in harness.benchmark()["workloads"]
               if harness.traffic(w["traffic"])["kind"] != "train_steps"]
TRAIN_CELLS = [w["name"] for w in harness.benchmark()["workloads"]
               if harness.traffic(w["traffic"])["kind"] == "train_steps"]


def _run(ctx):
    return harness.load_module("drivers", ctx.traffic["kind"]).run(ctx)


@pytest.mark.parametrize("cell", SERVE_CELLS)
def test_altered_token(cell, tmp_path, monkeypatch):
    from repro.launch import scheduler
    real, calls = scheduler._sample, [0]

    def altered(row, key, pos, temperature):
        calls[0] += 1
        tok = real(row, key, pos, temperature)
        return (tok + 1) % len(row) if calls[0] % 7 == 0 else tok

    ctx = smoke.context(cell, out_dir=tmp_path)
    monkeypatch.setattr(scheduler, "_sample", altered)
    out = _run(ctx)
    assert not out.correct, out.checks


def _wrap_step(monkeypatch, drv, wrap):
    real_build = drv.build

    def build(ctx):
        b = real_build(ctx)
        b.step_fn = wrap(b.step_fn)
        return b

    monkeypatch.setattr(drv, "build", build)


@pytest.mark.parametrize("cell", TRAIN_CELLS)
def test_step_returns_state_unchanged(cell, tmp_path, monkeypatch):
    ctx = smoke.context(cell, out_dir=tmp_path)
    drv = harness.load_module("drivers", ctx.traffic["kind"])

    def wrap(fn):
        def step(params, opt_state, batch, i):
            copies = jax.tree.map(jnp.copy, (params, opt_state))
            _, _, metrics = fn(*copies, batch, i)
            return params, opt_state, metrics
        return step

    _wrap_step(monkeypatch, drv, wrap)
    out = _run(ctx)
    assert not out.correct, out.checks


@pytest.mark.parametrize("cell", TRAIN_CELLS)
def test_half_the_batch_left_out(cell, tmp_path, monkeypatch):
    ctx = smoke.context(cell, out_dir=tmp_path)
    drv = harness.load_module("drivers", ctx.traffic["kind"])

    def wrap(fn):
        def step(params, opt_state, batch, i):
            half = {k: v[:v.shape[0] // 2] for k, v in batch.items()}
            return fn(params, opt_state, half, i)
        return step

    _wrap_step(monkeypatch, drv, wrap)
    out = _run(ctx)
    assert not out.correct, out.checks
