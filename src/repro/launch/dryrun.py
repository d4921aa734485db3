"""Multi-pod dry-run (deliverable e).

For every (architecture × input shape) cell, lower + compile the real
``train_step`` (train shapes) or ``serve_step`` (prefill/decode shapes)
against the production mesh — 16×16 single-pod and 2×16×16 multi-pod —
with every input a ShapeDtypeStruct (zero allocation).  Captures:

* ``compiled.memory_analysis()``  — bytes/device (proves it fits),
* ``compiled.cost_analysis()``    — FLOPs/bytes for §Roofline,
* collective bytes parsed from the post-SPMD HLO,
* HIDA-OPT pass reports + the derived plan.

Artifacts land in ``experiments/dryrun/<arch>__<shape>__<mesh>.json``.

Usage:
    JAX_PLATFORMS=cpu PYTHONPATH=src python -m repro.launch.dryrun \
        --arch smollm-135m --shape train_4k [--multi-pod] [--all] \
        [--strategy hida|naive|...]

``main()`` forces 512 host (CPU) devices before the first device query;
importing this module sets nothing.
"""
import argparse
import json
import os
import time
import traceback
from pathlib import Path

import jax

from ..configs import SHAPES, get_config, list_archs, shape_applicable
from ..core import MULTI_POD, SINGLE_POD, build_lm_graph, optimize
from ..core.graph import model_flops_6nd, step_flops
from ..core.plan import replicated_plan
from .hlo_analysis import collective_bytes, hlo_op_histogram
from .mesh import make_production_mesh, mesh_spec
from .steps import build_prefill_step, build_serve_step, build_train_step

ARTIFACT_DIR = Path(__file__).resolve().parents[3] / "experiments" / "dryrun"


def make_plan(arch: str, shape_name: str, multi_pod: bool,
              strategy: str = "hida", fsdp: bool | None = None):
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    mspec = mesh_spec(multi_pod)
    if fsdp is None:
        # Big configs need ZeRO-3 params/opt sharding to fit 16 GB HBM.
        fsdp = shape.mode == "train"
    if strategy == "naive":
        plan = replicated_plan(mspec, fsdp=fsdp)
        report = None
    else:
        ia = strategy in ("hida", "ia")
        ca = strategy in ("hida", "ca")
        g = build_lm_graph(cfg, shape)
        sched, plan, report = optimize(
            g, mspec, ia=ia, ca=ca, fsdp=fsdp,
            training=shape.mode == "train")
    return cfg, shape, plan, report


def run_cell(arch: str, shape_name: str, multi_pod: bool = False,
             strategy: str = "hida", save: bool = True,
             remat: str = "full", accum_steps: int = 1) -> dict:
    cfg, shape, plan, report = make_plan(arch, shape_name, multi_pod,
                                         strategy)
    ok, why = shape_applicable(cfg, shape)
    mesh_name = "2x16x16" if multi_pod else "16x16"
    result = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
              "strategy": strategy, "status": "ok"}
    if not ok:
        result.update(status="skipped", reason=why)
        if save:
            _save(result)
        return result

    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = mesh.devices.size
    t0 = time.perf_counter()
    try:
        with jax.set_mesh(mesh):
            if shape.mode == "train":
                step = build_train_step(cfg, shape, mesh, plan,
                                        remat=remat,
                                        accum_steps=accum_steps)
                lowered = step.fn.lower(*step.abstract_inputs)
            elif shape.mode == "prefill":
                fn, abs_in = build_prefill_step(cfg, shape, mesh, plan)
                lowered = fn.lower(*abs_in)
            else:
                step = build_serve_step(cfg, shape, mesh, plan)
                lowered = step.decode.lower(*step.abstract_inputs)
            t_lower = time.perf_counter() - t0
            compiled = lowered.compile()
            t_compile = time.perf_counter() - t0 - t_lower

        mem = compiled.memory_analysis()
        cost = compiled.cost_analysis()
        hlo = compiled.as_text()
        # The layer scan is a while loop; scale loop-resident collectives
        # by its trip count (XLA cost/byte counts see the body once).
        loop_trip = max(r for _, r in cfg.layer_groups())
        coll = collective_bytes(hlo)
        g = build_lm_graph(cfg, shape)
        tokens = shape.global_batch * (1 if shape.mode == "decode"
                                       else shape.seq_len)
        result.update({
            "analytic_flops": step_flops(g, shape.mode),
            "model_flops_6nd": model_flops_6nd(
                cfg, tokens) * (1.0 if shape.mode == "train" else 1 / 3),
            "loop_trip": loop_trip,
            "chips": chips,
            "lower_s": round(t_lower, 2),
            "compile_s": round(t_compile, 2),
            "memory_analysis": {
                k: int(getattr(mem, k, 0) or 0)
                for k in ("argument_size_in_bytes", "output_size_in_bytes",
                          "temp_size_in_bytes", "generated_code_size_in_bytes",
                          "alias_size_in_bytes")},
            "cost_analysis": {k: float(v) for k, v in (cost or {}).items()
                              if isinstance(v, (int, float))
                              and k in ("flops", "bytes accessed",
                                        "transcendentals",
                                        "optimal_seconds")},
            "collectives": coll.to_dict(loop_trip),
            "hlo_ops": hlo_op_histogram(hlo, top=12),
            "plan_rules": {k: list(v) for k, v in plan.rules.items()},
            "fsdp": plan.fsdp,
        })
        if report is not None:
            result["hida"] = {
                "nodes": report.meta.get("nodes"),
                "estimated_total_s": report.cost.total_s,
                "estimated_critical_s": report.cost.critical_s,
                "estimated_dominant": report.cost.dominant,
                "opt_time_s": round(report.compile_time_s, 2),
            }
    except Exception as e:  # a failure here is a bug in the system
        result.update(status="failed", error=f"{type(e).__name__}: {e}",
                      traceback=traceback.format_exc()[-2000:])
    if save:
        _save(result)
    return result


def _save(result: dict) -> None:
    ARTIFACT_DIR.mkdir(parents=True, exist_ok=True)
    name = (f"{result['arch']}__{result['shape']}__{result['mesh']}"
            + (f"__{result['strategy']}" if result.get("strategy", "hida")
               != "hida" else "") + ".json")
    (ARTIFACT_DIR / name).write_text(json.dumps(result, indent=2))


def main() -> None:
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None, choices=list_archs())
    ap.add_argument("--shape", default=None, choices=list(SHAPES))
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--all", action="store_true",
                    help="every (arch, shape) cell")
    ap.add_argument("--strategy", default="hida",
                    choices=("hida", "naive", "ia", "ca"))
    ap.add_argument("--remat", default="full",
                    choices=("full", "none", "dots"))
    ap.add_argument("--accum", type=int, default=1,
                    help="gradient-accumulation microbatches (train cells)")
    args = ap.parse_args()

    archs = list_archs() if args.all or args.arch is None else [args.arch]
    shapes = list(SHAPES) if args.all or args.shape is None else [args.shape]
    meshes = [False, True] if (args.both_meshes or args.all) \
        else [args.multi_pod]

    failures = 0
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                r = run_cell(arch, shape, multi_pod=mp,
                             strategy=args.strategy, remat=args.remat,
                             accum_steps=args.accum)
                status = r["status"]
                line = (f"{arch:22s} {shape:12s} {r['mesh']:8s} {status}")
                if status == "ok":
                    mem = r["memory_analysis"]
                    per_dev = (mem["argument_size_in_bytes"]
                               + mem["temp_size_in_bytes"])
                    line += (f" args+temp={per_dev/2**30:.2f}GiB/dev"
                             f" flops={r['cost_analysis'].get('flops', 0):.3g}"
                             f" coll={r['collectives']['total_bytes']/2**30:.3f}GiB"
                             f" compile={r['compile_s']:.1f}s")
                elif status == "failed":
                    failures += 1
                    line += f"  {r['error'][:120]}"
                print(line, flush=True)
    if failures:
        raise SystemExit(f"{failures} cells failed")


if __name__ == "__main__":
    main()
