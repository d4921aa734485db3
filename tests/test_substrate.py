"""Substrate tests: data pipeline, optimizer, gradient compression,
checkpoint/restart, elastic resharding, straggler policy (deliverable c)."""
import os
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.data import ShardedLoader, SyntheticCorpus
from repro.distributed import (CheckpointManager, StragglerMonitor,
                               gather_full_tree, reshard_checkpoint)
from repro.optim import AdamW, cosine_schedule
from repro.optim.compression import (EFState, compress, decompress,
                                     ef_compress_tree, ef_decompress_tree,
                                     init_ef_state)


# -- data ---------------------------------------------------------------------

def test_corpus_deterministic_and_host_disjoint():
    c = SyntheticCorpus(vocab=1024, seed=7)
    a = c.batch(step=3, shard=0, batch=4, seq=16)
    b = c.batch(step=3, shard=0, batch=4, seq=16)
    np.testing.assert_array_equal(a["tokens"], b["tokens"])
    other = c.batch(step=3, shard=1, batch=4, seq=16)
    assert not np.array_equal(a["tokens"], other["tokens"])
    # labels are next-token shifted
    np.testing.assert_array_equal(a["labels"][:, :-1], a["tokens"][:, 1:])


def test_loader_shards_partition_global_batch():
    c = SyntheticCorpus(vocab=64, seed=1)
    loaders = [ShardedLoader(c, global_batch=8, seq=8, n_hosts=4, host_id=h)
               for h in range(4)]
    batches = [ld.batch_at(0) for ld in loaders]
    assert all(b["tokens"].shape == (2, 8) for b in batches)
    # Elastic re-partition keeps determinism per (step, shard)
    re = loaders[0].reshard(n_hosts=2, host_id=1)
    assert re.batch_at(5)["tokens"].shape == (4, 8)


def test_loader_prefetch_iterator():
    c = SyntheticCorpus(vocab=64)
    ld = ShardedLoader(c, global_batch=4, seq=8)
    it = iter(ld)
    first = next(it)
    np.testing.assert_array_equal(first["tokens"], ld.batch_at(0)["tokens"])


# -- optimizer -------------------------------------------------------------------

def test_adamw_reduces_quadratic():
    opt = AdamW(lr=0.1, weight_decay=0.0)
    params = {"w": jnp.asarray([2.0, -3.0])}
    state = opt.init(params)

    def loss(p):
        return jnp.sum(jnp.square(p["w"]))

    for _ in range(100):
        g = jax.grad(loss)(params)
        params, state = opt.update(g, state, params)
    assert float(loss(params)) < 1e-2


def test_adamw_abstract_init_matches_concrete():
    opt = AdamW(moment_dtype="bf16")
    params = {"a": jnp.zeros((4, 8)), "b": jnp.zeros((3,))}
    abs_params = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), params)
    st_c = opt.init(params)
    st_a = opt.init(abs_params)
    for c, a in zip(jax.tree.leaves(st_c), jax.tree.leaves(st_a)):
        assert c.shape == a.shape and c.dtype == a.dtype


def test_cosine_schedule_shape():
    f = cosine_schedule(1.0, warmup=10, total=100)
    assert float(f(0)) == 0.0
    assert abs(float(f(10)) - 1.0) < 1e-6
    assert float(f(100)) < 0.01


# -- gradient compression ----------------------------------------------------------

def test_ef_compression_roundtrip_error_bounded():
    rng = np.random.default_rng(0)
    g = jnp.asarray(rng.normal(size=(256,)), jnp.float32)
    q, scale, resid = compress(g, jnp.zeros_like(g))
    deq = decompress(q, scale)
    assert float(jnp.max(jnp.abs(deq - g))) <= float(scale) + 1e-6
    # residual holds exactly the rounding error
    np.testing.assert_allclose(np.asarray(deq + resid), np.asarray(g),
                               rtol=1e-6, atol=1e-6)


def test_ef_feedback_corrects_bias_over_steps():
    """With error feedback the *accumulated* compressed sum tracks the
    accumulated true sum far better than memoryless quantization."""
    rng = np.random.default_rng(1)
    gs = [jnp.asarray(rng.normal(size=(64,)) * 1e-3, jnp.float32)
          for _ in range(50)]
    state = init_ef_state(gs[0])
    acc_ef = np.zeros(64)
    acc_nofb = np.zeros(64)
    resid = jnp.zeros((64,))
    for g in gs:
        q, s, resid = compress(g, resid)
        acc_ef += np.asarray(decompress(q, s))
        q2, s2, _ = compress(g, jnp.zeros((64,)))
        acc_nofb += np.asarray(decompress(q2, s2))
    true = np.sum([np.asarray(g) for g in gs], axis=0)
    assert np.abs(acc_ef - true).max() < np.abs(acc_nofb - true).max() + 1e-9


def test_ef_tree_roundtrip():
    grads = {"a": jnp.ones((8,)), "b": jnp.full((4,), -2.0)}
    state = init_ef_state(grads)
    q, s, new_state = ef_compress_tree(grads, state)
    deq = ef_decompress_tree(q, s)
    for k in grads:
        np.testing.assert_allclose(np.asarray(deq[k]),
                                   np.asarray(grads[k]), rtol=0.02)


# -- checkpoint / restart ------------------------------------------------------------

def test_checkpoint_save_restore_roundtrip(tmp_path):
    mgr = CheckpointManager(tmp_path)
    tree = {"p": jnp.arange(12.0).reshape(3, 4),
            "opt": {"m": jnp.ones((3, 4)) * 0.5}}
    mgr.save(10, tree, blocking=True)
    assert mgr.latest_step() == 10
    restored = mgr.restore(10, jax.tree.map(jnp.zeros_like, tree))
    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_checkpoint_atomic_commit_ignores_partial(tmp_path):
    mgr = CheckpointManager(tmp_path)
    tree = {"p": jnp.zeros((2,))}
    mgr.save(5, tree, blocking=True)
    # Simulate a torn write: directory without COMMITTED marker.
    (tmp_path / "step_000009").mkdir()
    assert mgr.latest_step() == 5


def test_checkpoint_gc_keeps_latest(tmp_path):
    mgr = CheckpointManager(tmp_path, keep=2)
    tree = {"p": jnp.zeros((2,))}
    for s in (1, 2, 3, 4):
        mgr.save(s, tree, blocking=True)
    assert mgr.steps() == [3, 4]


@pytest.mark.slow
def test_train_resume_bitwise(tmp_path):
    """Kill at step 6, restart, and verify the loss trajectory matches an
    uninterrupted run (checkpoint/restart fault tolerance)."""
    from repro.launch.train import main as train_main
    common = ["--arch", "smollm-135m", "--smoke", "--steps", "10",
              "--batch", "2", "--seq", "16", "--ckpt-every", "3"]
    ref = train_main(common + ["--ckpt-dir", str(tmp_path / "a")])
    out1 = train_main(common + ["--ckpt-dir", str(tmp_path / "b"),
                                "--simulate-preemption-at", "7"])
    assert out1.get("preempted_at") == 7
    out2 = train_main(common + ["--ckpt-dir", str(tmp_path / "b")])
    assert out2["resumed_from"] == 6
    np.testing.assert_allclose(out2["losses"][-1], ref["losses"][-1],
                               rtol=1e-4)


# -- elastic --------------------------------------------------------------------------

def test_elastic_reshard_checkpoint(tmp_path):
    tree = {"w": jnp.arange(16.0).reshape(4, 4)}
    mgr = CheckpointManager(tmp_path / "src", host_id=0, n_hosts=1)
    mgr.save(2, tree, blocking=True)
    reshard_checkpoint(tmp_path / "src", 2, tree, new_n_hosts=2,
                       dst_dir=tmp_path / "dst")
    for h in range(2):
        m2 = CheckpointManager(tmp_path / "dst", host_id=h, n_hosts=2)
        got = m2.restore(2, jax.tree.map(jnp.zeros_like, tree))
        np.testing.assert_array_equal(np.asarray(got["w"]),
                                      np.asarray(tree["w"]))


# -- straggler -------------------------------------------------------------------------

def test_straggler_detection_and_rebalance():
    mon = StragglerMonitor(n_hosts=4, ema=0.5, threshold=1.4,
                           evict_after=5)
    actions = []
    for step in range(10):
        times = {0: 1.0, 1: 1.0, 2: 1.0, 3: 3.0}   # host 3 is slow
        actions += mon.step(times)
    assert any(a["action"] == "rebalance" and a["host"] == 3
               for a in actions)
    assert any(a["action"] == "checkpoint_and_evict" and a["host"] == 3
               for a in actions)
    w = mon.shard_weights()
    assert w[3] < w[0]          # slow host gets a smaller shard


def test_straggler_recovery_clears_flag():
    mon = StragglerMonitor(n_hosts=2, ema=0.1, threshold=1.5)
    for _ in range(5):
        mon.step({0: 1.0, 1: 3.0})
    assert mon.stragglers() == [1]
    for _ in range(30):
        mon.step({0: 1.0, 1: 1.0})
    assert mon.stragglers() == []


@pytest.mark.slow
def test_grad_accumulation_matches_full_batch():
    """build_train_step(accum_steps=K) must produce (numerically) the
    same update as the full-batch step on a dense arch."""
    import jax.numpy as jnp
    from repro.configs import get_config
    from repro.configs.base import ShapeSpec
    from repro.core import MeshSpec, build_lm_graph, optimize
    from repro.launch.mesh import make_host_mesh
    from repro.launch.steps import build_train_step
    from repro.data import SyntheticCorpus

    cfg = get_config("smollm-135m", smoke=True)
    shape = ShapeSpec("t", 16, 4, "train")
    mspec = MeshSpec((("data", 1), ("model", 1)))
    g = build_lm_graph(cfg, shape)
    _, plan, _ = optimize(g, mspec, training=True)
    mesh = make_host_mesh((1, 1))
    corpus = SyntheticCorpus(cfg.vocab)
    batch = {k: jnp.asarray(v) for k, v in
             corpus.batch(0, 0, 4, 16).items()}

    outs = {}
    with jax.set_mesh(mesh):
        for accum in (1, 2):
            step = build_train_step(cfg, shape, mesh, plan, remat="none",
                                    accum_steps=accum)
            from repro.models.lm import LM
            from repro.optim import AdamW
            lm = LM(cfg, plan=plan, mesh=mesh, remat="none")
            params, _ = lm.init(jax.random.PRNGKey(0))
            opt_state = AdamW(
                moment_dtype=cfg.opt_moment_dtype).init(params)
            p2, _, metrics = step.fn(params, opt_state, batch)
            outs[accum] = p2
    for a, b in zip(jax.tree.leaves(outs[1]), jax.tree.leaves(outs[2])):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   rtol=2e-2, atol=2e-3)
