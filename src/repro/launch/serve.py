"""Production serving driver: continuous batching over ``decode_step``
with a persistent warm-start plan cache.

    PYTHONPATH=src python -m repro.launch.serve --arch smollm-135m \
        --smoke --slots 4 --requests 12 --gen-range 16 64 --static

Pipeline per invocation:

1. **Plan fetch** (:func:`fetch_plan`): the serving shape is quantized
   onto a bucket (:func:`repro.core.shape_bucket`) and looked up in the
   persistent :class:`repro.core.PlanCache` — a hit is a sub-ms fetch
   (statically re-verified against the mesh), a miss runs the DSE,
   warm-started from the nearest cached donor when one exists.  The
   cache root comes from ``--plan-cache`` or ``$REPRO_PLAN_CACHE``;
   without either the DSE still runs but nothing persists.  The plan is
   derived for the ``(data=n, model=1)`` mesh of the devices present and
   applied on that mesh.
2. **Continuous batching** (:class:`repro.launch.scheduler
   .ContinuousBatcher`): a request queue drained through a fixed-width
   decode batch with per-step admit/evict and shape-bucketed batched
   prefill.  ``--static`` additionally runs the lock-step wave baseline
   (:func:`repro.launch.scheduler.run_static`) for comparison.

RNG hygiene: the seed splits once into independent init / trace
streams, and every request gets its own fold_in-derived sampling stream
keyed by decode position (see ``scheduler._request_key``) — no key is
ever reused across draws, and a request's tokens do not depend on what
shares the batch with it.  MoE configs are served on the static path
(expert capacity couples batch rows; the batcher refuses them).
"""
from __future__ import annotations

import argparse
import os
import time
from dataclasses import dataclass

import jax
import numpy as np
from jax.sharding import Mesh

from ..configs import get_config, list_archs
from ..configs.base import ArchConfig, ShapeSpec
from ..core import (MeshSpec, PlanCache, PlanKey, analyze_plan,
                    build_lm_graph, fetch_or_optimize, shape_bucket)
from ..models.lm import LM
from .compile_cache import enable_compile_cache
from .mesh import host_mesh_and_spec
from .scheduler import ContinuousBatcher, Request, prefill_bucket, run_static


def fetch_plan(cfg, *, slots: int, s_max: int,
               cache_root: str | os.PathLike | None,
               mesh: MeshSpec,
               cache: PlanCache | None = None,
               optimize_kwargs: dict | None = None):
    """Serving-side compile: cache hit → warm re-DSE → cold DSE.

    Returns ``(plan, info)`` where ``info`` has the fetch ``source``
    (``hit``/``warm``/``cold``), wall ``fetch_ms``, the bucket, and the
    :class:`OptimizeReport` when a DSE ran."""
    cache = cache if cache is not None else PlanCache(cache_root)
    bucket = shape_bucket("decode", s_max, slots)
    key = PlanKey.make(cfg, mesh, bucket)
    shape = ShapeSpec(bucket, s_max, slots, "decode")
    t0 = time.perf_counter()
    plan, source, report = fetch_or_optimize(
        cache, key, mesh, lambda: build_lm_graph(cfg, shape),
        optimize_kwargs=optimize_kwargs)
    return plan, {"source": source, "fetch_ms": (time.perf_counter() - t0)
                  * 1e3, "bucket": bucket, "report": report,
                  "cache_stats": dict(cache.stats)}


def make_trace(cfg, n_requests: int, *, seed: int,
               prompt_len_range=(4, 48), gen_range=(16, 64),
               temperature: float = 0.0) -> list[dict]:
    """Deterministic mixed-length request trace.  A dedicated numpy
    stream (independent of model init and sampling keys) draws the
    shapes and prompt tokens."""
    rng = np.random.default_rng(seed)
    lo, hi = prompt_len_range
    glo, ghi = gen_range
    out = []
    for _ in range(n_requests):
        pl = int(rng.integers(lo, hi + 1))
        gen = int(rng.integers(glo, ghi + 1))
        prompt = (None if cfg.frontend == "audio_frames"
                  else rng.integers(0, cfg.vocab, pl).astype(np.int32))
        out.append({"prompt": prompt, "prompt_len": pl, "max_new": gen,
                    "temperature": temperature})
    return out


def _static_requests(trace: list[dict]) -> list[Request]:
    now = time.perf_counter()
    return [Request(rid=i, prompt_len=t["prompt_len"],
                    max_new=t["max_new"], prompt=t["prompt"],
                    temperature=t["temperature"], t_submit=now)
            for i, t in enumerate(trace)]


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-135m", choices=list_archs())
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--slots", type=int, default=4,
                    help="decode batch width (concurrent requests)")
    ap.add_argument("--requests", type=int, default=12)
    ap.add_argument("--prompt-len-range", type=int, nargs=2,
                    default=(4, 48), metavar=("LO", "HI"))
    ap.add_argument("--gen-range", type=int, nargs=2, default=(16, 64),
                    metavar=("LO", "HI"))
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--eos-id", type=int, default=None)
    ap.add_argument("--static", action="store_true",
                    help="also run the lock-step wave baseline")
    ap.add_argument("--warmup", type=int, default=0,
                    help="un-timed passes over the trace first, so the "
                    "reported numbers are steady-state (compile-free) — "
                    "what a long-lived endpoint actually serves at")
    ap.add_argument("--plan-cache", default=os.environ.get(
        "REPRO_PLAN_CACHE"), help="plan cache root dir "
        "(default: $REPRO_PLAN_CACHE; unset = no persistence)")
    ap.add_argument("--no-plan", action="store_true",
                    help="skip the DSE/plan fetch entirely")
    return ap.parse_args(argv)


@dataclass
class Server:
    """Everything one serving invocation runs on: the model with its
    plan applied on the mesh that runs, its parameters, and the trace."""
    cfg: ArchConfig
    mesh: Mesh
    plan_info: dict
    lm: LM
    params: dict
    trace: list[dict]
    s_max: int


def build(args: argparse.Namespace) -> Server:
    """Plan fetch + lint, model and parameter init, request trace.  The
    plan is derived for the mesh that runs and applied on it; parameters
    are made under that mesh."""
    cfg = get_config(args.arch, smoke=args.smoke)
    mesh, mspec = host_mesh_and_spec()
    pl_lo, pl_hi = args.prompt_len_range
    g_lo, g_hi = args.gen_range
    s_max = prefill_bucket(pl_hi) + g_hi

    plan, plan_info = (None, {"source": "skipped", "fetch_ms": 0.0}) \
        if args.no_plan else fetch_plan(
            cfg, slots=args.slots, s_max=s_max,
            cache_root=args.plan_cache, mesh=mspec)
    if plan_info["source"] != "skipped":
        print(f"[serve] plan: {plan_info['source']} in "
              f"{plan_info['fetch_ms']:.1f} ms "
              f"(bucket {plan_info['bucket']})")
    if plan is not None:
        # Pre-flight hazard lint: a DSE'd plan already carries the full
        # exit analysis (report.analyze), but cache hits skip the DSE —
        # re-lint the plan-scoped rules here so no serving path starts
        # on a hazardous plan unannounced.  Informational, not fatal:
        # the endpoint owner decides (the --strict lane is
        # ``python -m repro.lint``).
        report = plan_info.pop("report")
        if report is not None:
            plan_info["degradations"] = [str(d)
                                         for d in report.degradations]
        lint = analyze_plan(plan, mspec)
        plan_info["lint"] = {"ok": lint.ok,
                             "issues": [str(i) for i in lint.issues]}
        print(f"[serve] lint: {lint.summary()}")

    # RNG hygiene: one split at the top — params init and the request
    # trace never share a key, and sampling streams are derived
    # per-request inside the scheduler.
    k_init, _k_reserved = jax.random.split(jax.random.PRNGKey(args.seed))
    lm = LM(cfg, plan=plan, mesh=mesh, remat="none")
    with jax.set_mesh(mesh):
        params, _ = lm.init(k_init)
    trace = make_trace(cfg, args.requests, seed=args.seed,
                       prompt_len_range=(pl_lo, pl_hi),
                       gen_range=(g_lo, g_hi),
                       temperature=args.temperature)
    return Server(cfg, mesh, plan_info, lm, params, trace, s_max)


def main(argv=None) -> dict:
    args = parse_args(argv)
    enable_compile_cache()
    srv = build(args)
    lm, params, trace, s_max = srv.lm, srv.params, srv.trace, srv.s_max

    is_moe = any(ffn == "moe" for _, ffn in srv.cfg.layer_kinds())
    metrics: dict = {"arch": args.arch, "plan": srv.plan_info}
    if is_moe:
        print(f"[serve] {args.arch} has MoE layers — static path only "
              "(expert capacity couples batch rows)")
    with jax.set_mesh(srv.mesh):
        if not is_moe:
            def run_once():
                b = ContinuousBatcher(lm, params, slots=args.slots,
                                      s_max=s_max, seed=args.seed,
                                      eos_id=args.eos_id)
                for t in trace:
                    b.submit(t["prompt"], t["max_new"],
                             prompt_len=t["prompt_len"],
                             temperature=t["temperature"])
                return b.run()

            for _ in range(args.warmup):
                run_once()
            rep = run_once()
            metrics["continuous"] = rep.to_dict()
            metrics["outputs"] = [r.out for r in sorted(
                rep.requests, key=lambda r: r.rid)]
            print(f"[serve] continuous: {rep.generated} tokens / "
                  f"{len(rep.requests)} requests in {rep.wall_s:.2f}s "
                  f"({rep.to_dict()['tok_per_s']:.0f} tok/s, occupancy "
                  f"{rep.occupancy:.2f})")

        if args.static or is_moe:
            for _ in range(args.warmup):
                run_static(lm, params, _static_requests(trace),
                           seed=args.seed, s_max=s_max, slots=args.slots,
                           eos_id=args.eos_id)
            srep = run_static(lm, params, _static_requests(trace),
                              seed=args.seed, s_max=s_max,
                              slots=args.slots, eos_id=args.eos_id)
    if args.static or is_moe:
        metrics["static"] = srep.to_dict()
        print(f"[serve] static:     {srep.generated} tokens / "
              f"{len(srep.requests)} requests in {srep.wall_s:.2f}s "
              f"({srep.to_dict()['tok_per_s']:.0f} tok/s, occupancy "
              f"{srep.occupancy:.2f})")
        if "continuous" in metrics:
            ratio = (metrics["continuous"]["tok_per_s"]
                     / max(metrics["static"]["tok_per_s"], 1e-9))
            metrics["continuous_vs_static"] = ratio
            print(f"[serve] continuous/static throughput: {ratio:.2f}x")
    return metrics


if __name__ == "__main__":
    main()
