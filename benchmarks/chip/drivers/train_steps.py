"""Training steps: the jitted step of ``repro.launch.train.build``, fed
by the program's prefetching ``ShardedLoader`` over its
``SyntheticCorpus``, one ``float(loss)`` per step as ``train.main``
does.

Set-up builds the step and its state once and drives them through the
first ``check_steps`` steps with the window's own call and feed; their
losses, the first gradient (from the optimizer's first moment) and the
parameters' change are kept for the check.  The same objects then run
the window: steps start until ``--seconds`` have passed, and the rate
is the tokens of every step run over the time to the end of the last.
Afterwards the program's state is freed and the reference repeats the
first steps in float32 on the same rows.
"""
from __future__ import annotations

import argparse
import math
import time
from dataclasses import dataclass

import numpy as np

import harness
import reference
import weights


def _leaf_norms(tree) -> dict[str, float]:
    import jax
    import jax.numpy as jnp
    flat = weights.flatten(tree)
    norms = jax.jit(lambda f: {k: jnp.sqrt(jnp.sum(jnp.square(
        v.astype(jnp.float32)))) for k, v in f.items()})(flat)
    return {k: float(v) for k, v in norms.items()}


def lr_scale(step: int, total: int) -> float:
    """The job's schedule: linear warm-up over ``max(total // 20, 1)``
    steps, then a cosine to zero at ``total``."""
    warm = max(total // 20, 1)
    if step < warm:
        return step / warm
    prog = min(max((step - warm) / max(total - warm, 1), 0.0), 1.0)
    return 0.5 * (1.0 + math.cos(math.pi * prog))


@dataclass
class Built:
    cfg: object
    mesh: object
    lm: object
    opt: object
    step_fn: object


def build(ctx: harness.Context) -> Built:
    """``train.build`` for the job, and the check that the program's
    optimizer is the job's."""
    from repro.launch import train
    t = ctx.traffic
    args = argparse.Namespace(
        arch=ctx.conf["arch"], smoke=ctx.smoke, steps=t["schedule_steps"],
        batch=t["batch"], seq=t["seq"], lr=t["lr"], remat=t["remat"],
        fsdp=False)
    cfg, _shape, mesh, _info, lm, opt, step_fn = train.build(args)
    stated = {"b1": opt.b1, "b2": opt.b2, "eps": opt.eps,
              "weight_decay": opt.weight_decay, "clip": opt.grad_clip,
              "lr": opt.lr}
    if stated != dict(t["adamw"], lr=t["lr"]):
        raise RuntimeError(f"program's AdamW {stated} is not the job's "
                           f"{dict(t['adamw'], lr=t['lr'])}")
    bad = weights.check_tree(ctx.spec, lm.init(None, True)[0])
    if bad:
        raise RuntimeError(f"parameter layout differs: {bad}")
    return Built(cfg, mesh, lm, opt, step_fn)


class Job:
    """The step with its state and feed, from ``ctx.seed``."""

    def __init__(self, ctx: harness.Context, b: Built):
        import jax
        from repro.data import ShardedLoader, SyntheticCorpus
        t = ctx.traffic
        self.ctx, self.b, self.i = ctx, b, 0
        with jax.set_mesh(b.mesh):
            self.params = weights.make(ctx.spec, ctx.seed)
            self.opt_state = b.opt.init(self.params)
        self._loader = iter(ShardedLoader(
            SyntheticCorpus(b.cfg.vocab, seed=ctx.seed), t["batch"],
            t["seq"]))

    def step(self) -> tuple[dict, float]:
        """One step through the window's own call and feed."""
        import jax
        host = next(self._loader)
        with jax.set_mesh(self.b.mesh):
            batch = {k: jax.device_put(v) for k, v in host.items()}
            self.params, self.opt_state, metrics = self.b.step_fn(
                self.params, self.opt_state, batch, self.i)
            loss = float(metrics["loss"])
        self.i += 1
        return host, loss

    def first_steps(self):
        """The check's steps: rows fed, losses, leaf norms of the first
        gradient as the optimizer got it (its first moment over
        ``1 - b1``) and of the parameters' change over all of them."""
        import jax
        import jax.numpy as jnp
        fed, losses, g = [], [], None
        for _ in range(self.ctx.traffic["check_steps"]):
            host, loss = self.step()
            fed.append(host)
            losses.append(loss)
            if g is None:
                g = _leaf_norms(jax.tree.map(
                    lambda m: m.astype(jnp.float32) / (1 - self.b.opt.b1),
                    self.opt_state.mu))
        p0 = weights.make(self.ctx.spec, self.ctx.seed)
        d = _leaf_norms(jax.tree.map(
            lambda a, b: a.astype(jnp.float32) - b.astype(jnp.float32),
            self.params, p0))
        return fed, losses, g, d

    def close(self) -> None:
        """Free the program's state and stop the loader's producer."""
        self.params = self.opt_state = None
        self._loader.close()


def run(ctx: harness.Context) -> harness.Outcome:
    t = ctx.traffic
    job = Job(ctx, build(ctx))
    fed, losses, g_prog, d_prog = job.first_steps()

    prof = harness.Profile(ctx.out_dir / "trace") if ctx.trace else None
    t_open = time.perf_counter()
    setup_s = t_open - ctx.t_process
    c_open = ctx.compiles.n
    marks, n = {}, 0
    trace_at, trace_end = harness.trace_span(t, t_open, ctx.seconds)
    while (now := time.perf_counter()) - t_open < ctx.seconds:
        if prof and "t0" not in marks and now >= trace_at:
            prof.start()
            marks["t0"] = time.perf_counter()
        if prof and "t1" not in marks and now >= trace_end:
            marks["t1"] = time.perf_counter()
            prof.stop()
        job.step()
        n += 1
    t_end = time.perf_counter()
    if prof and "t1" not in marks:
        prof.stop()
    compiles = ctx.compiles.n - c_open
    mem = harness.memory_peak(1)
    job.close()

    rate = n * t["batch"] * t["seq"] / (t_end - t_open)
    ctx.note(f"window: {n} steps in {t_end - t_open:.6g} s, train_tok_s "
             f"{rate:.6g}; check losses {losses}")
    facts = {"window_s": t_end - t_open, "memory_peak_bytes": mem,
             "train_tok_s": rate, "seq": t["seq"], "spec": ctx.spec}
    if prof:
        facts["trace"] = prof.summary()

    ref = reference_steps(ctx.spec, t, ctx.seed, fed)
    ctx.note(f"reference losses {ref[0]}")
    nums = compare(losses, g_prog, d_prog, *ref)
    nums["repeated_rows"] = repeated_rows(fed)
    return harness.Outcome(
        e2e={"train_tok_s": rate}, facts=facts, attempted=n, failed=0,
        checks=[[k, v, ctx.limits[k]] for k, v in nums.items()],
        setup_s=setup_s, compiles_in_window=compiles)


def reference_steps(spec, t: dict, seed: int, fed: list[dict],
                    quant=None) -> tuple[list[float], dict, dict]:
    """The first steps again in float32 (or the control's precision),
    each new weight kept in its stated type: losses, the first clipped
    gradient's leaf norms, and the leaf norms of the parameters' change
    over all of them."""
    import jax
    import jax.numpy as jnp
    hp = t["adamw"]
    made = weights.make(spec, seed)
    store = jax.tree.map(lambda a: a.dtype, made)
    w0 = jax.tree.map(lambda a: a.astype(jnp.float32), made)
    del made
    w = w0
    mu = jax.tree.map(jnp.zeros_like, w)
    nu = jax.tree.map(jnp.zeros_like, w)
    losses, g_first = [], None
    for i, host in enumerate(fed):
        loss, grads = reference.loss_and_grad(
            spec, w, jnp.asarray(host["tokens"]), jnp.asarray(host["labels"]),
            quant, rows=t["reference_rows"])
        losses.append(float(loss))
        lr = t["lr"] * lr_scale(i, t["schedule_steps"])
        w, mu, nu, g = reference.adamw(
            w, grads, mu, nu, i + 1, lr, b1=hp["b1"], b2=hp["b2"],
            eps=hp["eps"], weight_decay=hp["weight_decay"], clip=hp["clip"],
            store=store)
        if g_first is None:
            g_first = _leaf_norms(g)
    d = _leaf_norms(jax.tree.map(jnp.subtract, w, w0))
    return losses, g_first, d


def gap(prog: dict, ref: dict, keep=None) -> float:
    """Worst leaf's gap between the program's and the reference's norm,
    over the larger of that leaf's reference norm and the median leaf's."""
    keys = [k for k in ref if keep is None or k in keep]
    med = float(np.median([ref[k] for k in keys]))
    return max(abs(prog[k] - ref[k]) / max(ref[k], med) for k in keys)


def compare(losses, g, d, ref_losses, ref_g, ref_d) -> dict[str, float]:
    """The numbers compared.  Leaves whose reference gradient is under a
    thousandth of the median leaf's move by round-off alone, and are
    left out of the change."""
    med = float(np.median(list(ref_g.values())))
    moving = {k for k, v in ref_g.items() if v >= 1e-3 * med}
    return {"loss_gap": max(abs(a - b) / abs(b)
                            for a, b in zip(losses, ref_losses)),
            "grad_norm_gap": gap(g, ref_g),
            "change_norm_gap": gap(d, ref_d, moving)}


def repeated_rows(fed: list[dict]) -> int:
    """Rows fed to the check's steps that repeat an earlier one."""
    rows = np.concatenate([h["tokens"] for h in fed])
    return len(rows) - len({r.tobytes() for r in rows})
