"""Fault-tolerant training driver (deliverable b: end-to-end example).

Wires every substrate layer together: HIDA-OPT plan → pjit train step,
deterministic sharded data pipeline, AdamW, async checkpointing with
auto-resume, straggler monitoring, and (optionally) simulated preemption
to exercise the restart path.

On this CPU container run the reduced configs::

    PYTHONPATH=src python -m repro.launch.train --arch smollm-135m \
        --smoke --steps 50 --batch 8 --seq 64

On an accelerator the same driver runs the full config (drop
``--smoke``): the plan is derived for, and applied on, the
``(data=n_devices, model=1)`` mesh of the devices present.
"""
from __future__ import annotations

import argparse
import time
from pathlib import Path

import jax
import numpy as np

from ..configs import get_config, list_archs
from ..configs.base import ShapeSpec
from ..core import analyze_plan, build_lm_graph, optimize
from ..data import ShardedLoader, SyntheticCorpus
from ..distributed import CheckpointManager, StragglerMonitor
from ..models.lm import LM
from ..optim import AdamW, cosine_schedule
from .compile_cache import enable_compile_cache
from .mesh import host_mesh_and_spec


def build(args):
    cfg = get_config(args.arch, smoke=args.smoke)
    shape = ShapeSpec("cli", args.seq, args.batch, "train")

    mesh, mspec = host_mesh_and_spec()
    g = build_lm_graph(cfg, shape)
    sched, plan, report = optimize(g, mspec, fsdp=args.fsdp)
    lint = analyze_plan(plan, mspec)
    plan_info = {"degradations": [str(d) for d in report.degradations],
                 "lint": {"ok": lint.ok,
                          "issues": [str(i) for i in lint.issues]}}
    lm = LM(cfg, plan=plan, remat=args.remat)
    opt = AdamW(lr=args.lr, moment_dtype=cfg.opt_moment_dtype)
    lr_fn = cosine_schedule(1.0, warmup=max(args.steps // 20, 1),
                            total=args.steps)

    def train_step(params, opt_state, batch, step):
        (loss, metrics), grads = jax.value_and_grad(
            lm.loss_fn, has_aux=True)(params, batch)
        params, opt_state = opt.update(grads, opt_state, params,
                                       lr_scale=lr_fn(step))
        return params, opt_state, metrics

    return cfg, shape, mesh, plan_info, lm, opt, jax.jit(
        train_step, donate_argnums=(0, 1))


def main(argv=None) -> dict:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-135m", choices=list_archs())
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--remat", default="none")
    ap.add_argument("--fsdp", action="store_true")
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=20)
    ap.add_argument("--simulate-preemption-at", type=int, default=-1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg, shape, mesh, plan_info, lm, opt, step_fn = build(args)
    corpus = SyntheticCorpus(cfg.vocab, seed=args.seed)
    loader = ShardedLoader(corpus, args.batch, args.seq)
    ckpt = CheckpointManager(args.ckpt_dir)
    monitor = StragglerMonitor(n_hosts=1)

    params, _ = lm.init(jax.random.PRNGKey(args.seed))
    opt_state = opt.init(params)

    start, restored = 0, False
    latest = ckpt.latest_step()
    if latest is not None:
        start = latest
        state = ckpt.restore(latest, {"params": params,
                                      "opt": opt_state})
        params, opt_state = state["params"], state["opt"]
        restored = True
        print(f"[train] resumed from step {latest}")

    losses = []
    with jax.set_mesh(mesh):
        for step in range(start, args.steps):
            if step == args.simulate_preemption_at and not restored:
                print(f"[train] simulated preemption at step {step}")
                ckpt.wait()
                return {"preempted_at": step, "losses": losses,
                        "plan": plan_info}
            t0 = time.perf_counter()
            batch = {k: jax.device_put(v)
                     for k, v in loader.batch_at(step).items()}
            params, opt_state, metrics = step_fn(
                params, opt_state, batch, step)
            loss = float(metrics["loss"])
            losses.append(loss)
            dt = time.perf_counter() - t0
            monitor.step({0: dt})
            if step % 10 == 0 or step == args.steps - 1:
                print(f"[train] step {step:5d} loss {loss:.4f} "
                      f"({dt*1e3:.0f} ms)", flush=True)
            if args.ckpt_every and (step + 1) % args.ckpt_every == 0:
                ckpt.save(step + 1, {"params": params, "opt": opt_state})
    ckpt.wait()
    return {"final_loss": losses[-1] if losses else None,
            "losses": losses, "resumed_from": start, "plan": plan_info}


if __name__ == "__main__":
    out = main()
    print(f"[train] done: {out.get('final_loss')}")
