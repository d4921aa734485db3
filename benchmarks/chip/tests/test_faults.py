"""A run with its timed path broken underneath comes out not correct:
the harness's look for a chip is skipped, everything else of a run is
driven at smoke size on the CPU.  Faults a cell can have: a token
altered where it is produced, a step that returns its state unchanged,
half of the batch left out, and on more than one chip the exchange
between chips left out."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import harness
import smoke

SERVE_CELLS = [w["name"] for w in harness.benchmark()["workloads"]
               if harness.traffic(w["traffic"])["kind"] != "train_steps"]
TRAIN_CELLS = [w["name"] for w in harness.benchmark()["workloads"]
               if harness.traffic(w["traffic"])["kind"] == "train_steps"]
MESH_TRAIN_CELLS = [w["name"] for w in harness.benchmark()["workloads"]
                    if w["name"] in TRAIN_CELLS and w["chips"] > 1]


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


def _by_data_shard(outs: list, like, mesh):
    """Each device's part of each leaf from ``outs[k]``, ``k`` its place
    on the mesh's data axis (a part that several data shards hold, from
    the first of them)."""
    axis = mesh.axis_names.index("data")

    def one(ref, *parts):
        out = np.array(parts[0])
        owners = ref.sharding.devices_indices_map(ref.shape).items()
        for dev, idx in sorted(owners, key=lambda kv: -np.argwhere(
                mesh.devices == kv[0])[0][axis]):
            k = np.argwhere(mesh.devices == dev)[0][axis]
            out[idx] = np.asarray(parts[k])[idx]
        return jax.device_put(out, ref.sharding)

    return jax.tree.map(one, like, *outs)


@pytest.mark.parametrize("cell", MESH_TRAIN_CELLS)
def test_exchange_between_chips_left_out(cell, tmp_path, monkeypatch):
    """Each data shard updates its part of the state from its own rows
    alone, as a step whose gradient is never exchanged over the data
    axis would."""
    ctx = smoke.context(cell, out_dir=tmp_path)
    drv = harness.load_module("drivers", ctx.traffic["kind"])
    d = ctx.mesh.shape["data"]

    def wrap(fn):
        def step(params, opt_state, batch, i):
            rows = len(batch["tokens"]) // d
            outs = [fn(*jax.tree.map(jnp.copy, (params, opt_state)),
                       {k: v[j * rows:(j + 1) * rows]
                        for k, v in batch.items()}, i) for j in range(d)]
            state = _by_data_shard([o[:2] for o in outs],
                                   (params, opt_state), ctx.mesh)
            return (*state, outs[0][2])
        return step

    _wrap_step(monkeypatch, drv, wrap)
    out = _run(ctx)
    assert not out.correct, out.checks
