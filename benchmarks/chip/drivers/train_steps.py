"""Training steps: the jitted step that ``repro.launch.train.build``
makes, here for the cell's config as cut, planned by ``optimize()`` for
the cell's mesh (the mix's ``mesh`` and ``fsdp``), fed by the program's
prefetching ``ShardedLoader`` over its ``SyntheticCorpus``, one
``float(loss)`` per step as ``train.main`` does.

Set-up builds the step and its state once, the weights and the
optimizer's state made directly in the plan's shardings, and drives them
through the first ``check_steps`` steps with the window's own call and
feed; their losses, the first gradient (from the optimizer's first
moment) and the parameters' change are kept for the check.  The same
objects then run the window: steps start until ``--seconds`` have
passed, and the rate is the tokens of every step run over the time to
the end of the last.  Afterwards the program's state is freed and the
family's reference repeats the first steps in float32 on the same rows,
its arrays placed as the program's were.
"""
from __future__ import annotations

import functools
import math
import time
from dataclasses import dataclass

import numpy as np

import harness
import readers
import reference
import weights


@functools.cache
def _norms():
    import jax
    import jax.numpy as jnp

    def norms(a, b, div):
        f32 = jnp.float32
        return {k: jnp.sqrt(jnp.sum(jnp.square(
            (v.astype(f32) - (0 if b is None else b[k].astype(f32))) / div)))
            for k, v in a.items()}

    return jax.jit(norms, static_argnums=2)


def _leaf_norms(tree, minus=None, div: float = 1.0) -> dict[str, float]:
    """Norm of each leaf of ``(tree - minus) / div`` in f32, in one call."""
    out = _norms()(weights.flatten(tree),
                   None if minus is None else weights.flatten(minus), div)
    return {k: float(v) for k, v in out.items()}


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
    layout: dict                  # the family's, of the weights
    shardings: dict               # the parameters', under the plan
    opt_shardings: object
    batch_sharding: dict


def plan_problems(plan, report, lint, mesh_spec, cfg, shardings) -> list[str]:
    """A degraded or lint-dirty plan, heads that do not split evenly,
    and on more than one chip a weight leaf whole on every chip."""
    out = [f"plan degraded: {d}" for d in report.degradations]
    out += [f"plan lint: {i}" for i in lint.issues]
    for dim, size in (("heads", cfg.n_heads), ("kv_heads", cfg.n_kv_heads)):
        f = math.prod(mesh_spec.size(a) for a in plan.rules.get(dim, ()))
        if size % f:
            out.append(f"{dim}={size} split {f} ways")
    if mesh_spec.chips > 1:
        out += [f"{p}: whole on every chip" for p, sh in
                weights.flatten(shardings).items() if sh.is_fully_replicated]
    return out


def build(ctx: harness.Context) -> Built:
    """``train.build``'s step for the cell's config, mesh and ``fsdp``,
    jitted with the plan's shardings; and the checks that the program's
    optimizer is the job's, its parameter tree the family's layout and
    its plan sound."""
    import jax
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P
    from repro.configs.base import ShapeSpec
    from repro.core import analyze_plan, build_lm_graph, optimize
    from repro.launch.steps import batch_specs, sharding_tree
    from repro.models.lm import LM
    from repro.optim import AdamW, cosine_schedule
    t, cfg, mesh = ctx.traffic, ctx.arch_cfg, ctx.mesh
    shape = ShapeSpec("cli", t["seq"], t["batch"], "train")
    _, plan, report = optimize(build_lm_graph(cfg, shape), ctx.mesh_spec,
                               fsdp=ctx.fsdp)
    lint = analyze_plan(plan, ctx.mesh_spec)
    lm = LM(cfg, plan=plan, remat=t["remat"])
    opt = AdamW(lr=t["lr"], moment_dtype=cfg.opt_moment_dtype)
    lr_fn = cosine_schedule(1.0, warmup=max(t["schedule_steps"] // 20, 1),
                            total=t["schedule_steps"])

    def train_step(params, opt_state, batch, step):
        (loss, metrics), grads = jax.value_and_grad(
            lm.loss_fn, has_aux=True)(params, batch)
        params, opt_state = opt.update(grads, opt_state, params,
                                       lr_scale=lr_fn(step))
        return params, opt_state, metrics

    stated = {"b1": opt.b1, "b2": opt.b2, "eps": opt.eps,
              "weight_decay": opt.weight_decay, "clip": opt.grad_clip,
              "lr": opt.lr}
    if stated != dict(t["adamw"], lr=t["lr"]):
        raise RuntimeError(f"program's AdamW {stated} is not the job's "
                           f"{dict(t['adamw'], lr=t['lr'])}")
    abstract, dims = lm.init(None, True)
    lay = harness.family(ctx.spec.family).layout(ctx.spec)
    bad = weights.check_tree(lay, abstract)
    if bad:
        raise RuntimeError(f"parameter layout differs: {bad}")
    p_sh = sharding_tree(dims, mesh, plan, weight=True, shapes_tree=abstract)
    bad = plan_problems(plan, report, lint, ctx.mesh_spec, cfg, p_sh)
    if bad:
        raise RuntimeError(f"plan for {ctx.mesh_spec}: {bad}")
    whole = NamedSharding(mesh, P())
    o_sh = type(opt.init(abstract))(whole, p_sh, p_sh)
    b_sh = sharding_tree(batch_specs(cfg, shape)[1], mesh, plan)
    step_fn = jax.jit(train_step, in_shardings=(p_sh, o_sh, b_sh, whole),
                      out_shardings=(p_sh, o_sh, None),
                      donate_argnums=(0, 1))
    return Built(cfg, mesh, lm, opt, step_fn, lay, p_sh, o_sh, b_sh)


class Job:
    """The step with its state and feed, from ``ctx.seed``."""

    def __init__(self, ctx: harness.Context, b: Built):
        import jax
        from repro.data import ShardedLoader, SyntheticCorpus
        t = ctx.traffic
        self.ctx, self.b, self.i = ctx, b, 0
        with jax.set_mesh(b.mesh):
            self.params = weights.make(b.layout, ctx.seed, b.shardings)
            self.opt_state = weights.zeros(
                b.opt.init(b.lm.init(None, True)[0]), b.opt_shardings)
        self._loader = iter(ShardedLoader(
            SyntheticCorpus(b.cfg.vocab, seed=ctx.seed), t["batch"],
            t["seq"]))

    def step(self) -> tuple[dict, float]:
        """One step through the window's own call and feed."""
        import jax
        host = next(self._loader)
        with jax.set_mesh(self.b.mesh):
            batch = jax.device_put(host, self.b.batch_sharding)
            self.params, self.opt_state, metrics = self.b.step_fn(
                self.params, self.opt_state, batch, self.i)
            loss = float(metrics["loss"])
        self.i += 1
        return host, loss

    def first_steps(self):
        """The check's steps: rows fed, losses, leaf norms of the first
        gradient as the optimizer got it (its first moment over
        ``1 - b1``) and of the parameters' change over all of them."""
        fed, losses, g = [], [], None
        for _ in range(self.ctx.traffic["check_steps"]):
            host, loss = self.step()
            fed.append(host)
            losses.append(loss)
            if g is None:
                g = _leaf_norms(self.opt_state.mu, div=1 - self.b.opt.b1)
        p0 = weights.make(self.b.layout, self.ctx.seed, self.b.shardings)
        d = _leaf_norms(self.params, p0)
        return fed, losses, g, d

    def close(self) -> None:
        """Free the program's state and stop the loader's producer."""
        self.params = self.opt_state = None
        self._loader.close()


def run(ctx: harness.Context) -> harness.Outcome:
    t = ctx.traffic
    built = build(ctx)
    job = Job(ctx, built)
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
    mem = harness.memory_peak(ctx.chips)
    job.close()

    rate = n * t["batch"] * t["seq"] / (t_end - t_open)
    ctx.note(f"window: {n} steps in {t_end - t_open:.6g} s, train_tok_s "
             f"{rate:.6g}; check losses {losses}")
    facts = {"window_s": t_end - t_open, "memory_peak_bytes": mem,
             "train_tok_s": rate, "seq": t["seq"], "spec": ctx.spec}
    if prof:
        facts["trace"] = prof.summary()
        ops = facts["trace"].ops_of(readers.TRAIN)
        coll = sorted(((s, op) for op, s in ops.items()
                       if op.startswith(readers.COLLECTIVES)), reverse=True)
        ctx.note(f"train step: {len(ops)} operations, collectives "
                 f"{[(op, s) for s, op in coll[:6]]}")

    ref = reference_steps(ctx.spec, t, ctx.seed, fed, built)
    ctx.note(f"reference losses {ref[0]}")
    nums = compare(losses, g_prog, d_prog, *ref)
    nums["repeated_rows"] = repeated_rows(fed)
    return harness.Outcome(
        e2e={"train_tok_s": rate}, facts=facts, attempted=n, failed=0,
        checks=[[k, v, ctx.limits[k]] for k, v in nums.items()],
        setup_s=setup_s, compiles_in_window=compiles)


def reference_steps(spec, t: dict, seed: int, fed: list[dict], b: Built,
                    quant=None) -> tuple[list[float], dict, dict]:
    """The first steps again in float32 (or the control's precision),
    each new weight kept in its stated type, every array placed as the
    program's was: losses, the first clipped gradient's leaf norms, and
    the leaf norms of the parameters' change over all of them.  The
    moments wait on the host while a gradient is taken, so that the
    weights, the gradient and a block's gradient fit beside them."""
    import jax
    import jax.numpy as jnp
    f32 = jnp.float32
    fam, hp = harness.family(spec.family), t["adamw"]
    made = weights.make(b.layout, seed, b.shardings)
    store = jax.tree.map(lambda a: a.dtype, made)
    w = jax.jit(lambda m: jax.tree.map(lambda a: a.astype(f32), m),
                out_shardings=b.shardings)(made)
    del made
    step = jax.jit(functools.partial(
        reference.adamw, b1=hp["b1"], b2=hp["b2"], eps=hp["eps"],
        weight_decay=hp["weight_decay"], clip=hp["clip"], store=store),
        out_shardings=(b.shardings,) * 4, donate_argnums=(0, 1, 2, 3))
    losses, g_first, moments = [], None, None
    for i, host in enumerate(fed):
        loss, grads = fam.loss_and_grad(
            spec, w, host["tokens"], host["labels"], quant,
            rows=t["reference_rows"],
            batch_sharding=b.batch_sharding["tokens"])
        losses.append(float(loss))
        mu, nu = ((weights.zeros(w), weights.zeros(w)) if moments is None
                  else jax.device_put(moments, (b.shardings, b.shardings)))
        lr = t["lr"] * lr_scale(i, t["schedule_steps"])
        w, mu, nu, g = step(w, grads, mu, nu, i + 1, lr)
        if g_first is None:
            g_first = _leaf_norms(g)
        del grads, g
        moments = jax.device_get((mu, nu)) if i + 1 < len(fed) else None
        del mu, nu
    d = _leaf_norms(w, weights.make(b.layout, seed, b.shardings))
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
