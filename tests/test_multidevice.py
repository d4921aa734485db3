"""Multi-device tests run in subprocesses (the suite itself must see one
device; XLA locks the device count at first jax import).

Covers: (a) a reduced-mesh dry-run — lower+compile the real train step on
a (4,2) mesh with a HIDA plan, collectives present; (b) the GPipe
pipeline runtime over a 4-way stage axis vs the sequential oracle;
(c) shard_map EP MoE vs the global oracle on a (2,2) mesh; (d)
``chip_smoke.py --chips 4``'s phase at smoke size on a (2,2) mesh."""
import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(n_devices: int, code: str) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = (f"--xla_force_host_platform_device_count="
                        f"{n_devices}")
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         env=env, capture_output=True, text=True,
                         timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


@pytest.mark.slow
def test_dryrun_reduced_mesh_compiles():
    out = _run(8, """
        import jax
        from repro.configs import get_config
        from repro.configs.base import ShapeSpec
        from repro.core import MeshSpec, build_lm_graph, optimize
        from repro.launch.steps import build_train_step
        from repro.launch.hlo_analysis import collective_bytes
        from repro.launch.mesh import make_mesh

        cfg = get_config("smollm-135m")
        shape = ShapeSpec("t", 512, 16, "train")
        mspec = MeshSpec((("data", 4), ("model", 2)))
        g = build_lm_graph(cfg, shape)
        sched, plan, rep = optimize(g, mspec, training=True)
        mesh = make_mesh((4, 2), ("data", "model"))
        with jax.set_mesh(mesh):
            step = build_train_step(cfg, shape, mesh, plan)
            compiled = step.fn.lower(*step.abstract_inputs).compile()
        stats = collective_bytes(compiled.as_text())
        assert stats.total_bytes > 0, "expected collectives on a 4x2 mesh"
        mem = compiled.memory_analysis()
        print("OK", stats.count_by_kind, mem.temp_size_in_bytes)
    """)
    assert "OK" in out


@pytest.mark.slow
def test_gpipe_pipeline_matches_sequential():
    out = _run(4, """
        import jax, jax.numpy as jnp, numpy as np
        from repro.core.pipeline import PipelineConfig, gpipe
        from repro.launch.mesh import make_mesh

        S, M, B, D = 4, 6, 2, 8
        mesh = make_mesh((S,), ("pod",))
        rng = np.random.default_rng(0)
        Ws = jnp.asarray(rng.normal(size=(S, D, D)) * 0.3, jnp.float32)
        mb = jnp.asarray(rng.normal(size=(M, B, D)), jnp.float32)

        def stage_fn(w, x, sid):
            return jnp.tanh(x @ w)

        run = gpipe(stage_fn, PipelineConfig(S, M), mesh, None, None)
        got = np.asarray(run(Ws, mb))

        ref = mb
        for s in range(S):
            ref = jnp.tanh(ref @ Ws[s])
        np.testing.assert_allclose(got, np.asarray(ref), rtol=1e-5,
                                   atol=1e-5)
        print("OK pipeline")
    """)
    assert "OK pipeline" in out


@pytest.mark.slow
def test_ep_moe_matches_global():
    out = _run(4, """
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs import get_config
        from repro.launch.mesh import make_mesh
        from repro.models.moe import moe_ffn, moe_ffn_ep
        from repro.models.layers import ParamBuilder
        from repro.models.moe import init_moe

        cfg = get_config("deepseek-v2-smoke" if False else
                         "deepseek-v2-236b", smoke=True)
        # dropless regime so local-vs-global capacity enforcement agrees
        object.__setattr__(cfg.moe, "capacity_factor", 8.0)
        pb = ParamBuilder(jax.random.PRNGKey(0))
        init_moe(pb, "m", cfg)
        p = pb.params["m"]
        B, S, D = 4, 8, cfg.d_model
        x = jax.random.normal(jax.random.PRNGKey(1), (B, S, D),
                              jnp.float32).astype(jnp.bfloat16)
        mesh = make_mesh((2, 2), ("data", "model"))
        ref, aux_ref = moe_ffn(x, p, cfg, lambda t, d, s=None: t)
        with jax.set_mesh(mesh):
            got, aux = jax.jit(lambda x, p: moe_ffn_ep(
                x, p, cfg, ("data",), ("model",), (), mesh))(x, p)
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(ref, np.float32),
            rtol=0.1, atol=0.25)
        print("OK ep moe", float(aux.dropped_fraction))
    """)
    assert "OK ep moe" in out


def test_chip_smoke_multichip_phase():
    out = _run(4, f"""
        import sys
        sys.path.insert(0, {REPO!r})
        import chip_smoke
        problems = chip_smoke.multichip_phase(0, 8, 64, smoke=True)
        assert problems == [], problems
        print("OK chips4")
    """)
    assert "OK chips4" in out
