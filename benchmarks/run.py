"""Benchmark runner — one suite per paper table/figure (deliverable d).

Prints ``name,us_per_call,derived`` CSV.  Suites:

* ``case_study``      — Table 2 (expert vs exhaustive vs HIDA)
* ``polybench``       — Table 7 (C++ kernels as dataflow graphs)
* ``models``          — Table 8 (the 10-arch zoo, HIDA vs naive)
* ``ablation_iaca``   — Fig. 11 (IA+CA vs IA vs CA vs naive sweep)
* ``ablation_scale``  — Fig. 10 (parallel factor × tile size)
* ``train_smoke``     — real measured CPU training throughput (smoke cfg)
* ``compile_time``    — ``optimize()`` wall time per config (the compiler's
  own perf trajectory; also emits ``BENCH_compile_time.json``).  Run as
  ``python -m benchmarks.bench_compile_time --compare
  BENCH_compile_time.json`` to use it as a CI gate that exits nonzero on
  a >2× wall-time (or any QoR) regression against the committed baseline.
* ``serve``           — serving path: continuous-batching vs static-wave
  throughput + plan-cache tiers (cold/warm DSE wall, hit fetch time) on
  every zoo config; emits ``BENCH_serve.json`` with its own
  ``--compare`` gate (``python -m benchmarks.bench_serve --compare
  BENCH_serve.json``).
* ``lint``            — the ``python -m repro.lint`` hazard sweep over
  every config + ``synth_1k`` (static dataflow analysis:
  deadlock/FIFO-depth, shard races, write ordering, index invariants),
  plus a ``ruff check`` row when ruff is installed (skipped otherwise —
  the config lives in ``ruff.toml``).  Per-arm ``analyze_s`` is gated
  by ``bench_compile_time --compare`` like ``verify_s``.

``python -m benchmarks.run [--suite NAME] [--fast]``
"""
from __future__ import annotations

import argparse
import sys
import time
from dataclasses import dataclass, field


@dataclass
class Report:
    rows: list = field(default_factory=list)

    def add(self, name: str, us_per_call: float, derived: str = "") -> None:
        self.rows.append((name, us_per_call, derived))
        print(f"{name},{us_per_call:.2f},{derived}", flush=True)


def bench_train_smoke(report) -> None:
    import jax
    from repro.launch.train import main as train_main
    t0 = time.perf_counter()
    out = train_main(["--arch", "smollm-135m", "--smoke", "--steps", "12",
                      "--batch", "4", "--seq", "64", "--ckpt-every", "0",
                      "--ckpt-dir", "/tmp/repro_bench_ckpt"])
    dt = time.perf_counter() - t0
    toks = 12 * 4 * 64
    report.add("train_smoke/smollm-135m", us_per_call=dt / 12 * 1e6,
               derived=f"tok_per_s={toks/dt:.0f}|"
                       f"final_loss={out['final_loss']:.3f}")


def bench_lint(report, fast: bool = False) -> None:
    """Hazard-lint every config (the CI lane `python -m repro.lint`
    drives the same code); nonzero findings land in the derived column
    rather than aborting the suite.  Ruff is optional tooling — absent
    in the pinned image — so its row degrades to a skip note."""
    import shutil
    import subprocess

    from repro.configs import list_archs
    from repro.lint import lint_one

    targets = (list_archs()[:3] if fast else list_archs()) + ["synth_1k"]
    for name in targets:
        res = lint_one(name)
        report.add(f"lint/{name}", us_per_call=res["wall_s"] * 1e6,
                   derived=f"ok={res['ok']}|errors={len(res['errors'])}"
                           f"|warnings={len(res['warnings'])}"
                           f"|checks={res['checks']}"
                           f"|analyze_ms={res['analyze_s'] * 1e3:.3f}")
    ruff = shutil.which("ruff")
    if ruff is None:
        report.add("lint/ruff", 0.0,
                   derived="skipped (ruff not installed; see ruff.toml)")
    else:
        t0 = time.perf_counter()
        proc = subprocess.run([ruff, "check", "src", "tests", "benchmarks"],
                              capture_output=True, text=True)
        report.add("lint/ruff",
                   us_per_call=(time.perf_counter() - t0) * 1e6,
                   derived=f"rc={proc.returncode}")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--suite", default="all",
                    choices=("all", "case_study", "polybench", "models",
                             "ablation_iaca", "ablation_scale",
                             "train_smoke", "compile_time", "serve",
                             "lint"))
    ap.add_argument("--fast", action="store_true",
                    help="skip the slower model-zoo arms")
    args = ap.parse_args()

    report = Report()
    print("name,us_per_call,derived")

    want = (lambda s: args.suite in ("all", s))
    if want("case_study"):
        from .bench_case_study import run as r
        r(report)
    if want("polybench"):
        from .bench_kernels_polybench import run as r
        r(report)
    if want("models"):
        from .bench_models import run as r
        archs = (["smollm-135m", "jamba-v0.1-52b", "deepseek-v2-236b"]
                 if args.fast else None)
        r(report, archs=archs)
    if want("ablation_iaca"):
        from .bench_ablation_iaca import run as r
        r(report, factors=(16, 256) if args.fast else (4, 16, 64, 256))
    if want("ablation_scale"):
        from .bench_ablation_scale import run as r
        r(report, factors=(16, 256) if args.fast else (4, 16, 64, 256))
    if want("train_smoke"):
        bench_train_smoke(report)
    if want("compile_time"):
        from .bench_compile_time import run as r
        r(report, fast=args.fast)
    if want("serve"):
        from .bench_serve import run as r
        r(report, fast=args.fast)
    if want("lint"):
        bench_lint(report, fast=args.fast)
    print(f"# {len(report.rows)} benchmark rows", file=sys.stderr)


if __name__ == "__main__":
    main()
