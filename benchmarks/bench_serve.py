"""Serving-path benchmark: continuous batching + the persistent plan
cache (the production serving deliverable).

Two arm families, one JSON (``BENCH_serve.json``):

* ``serve/<arch>`` — steady-state throughput of the continuous batcher
  vs. the lock-step static-wave baseline at the *same* hardware batch
  width, on a mixed-length request trace (the regime continuous
  batching exists for: short requests finish and their slots are
  refilled while long ones keep decoding).  One un-timed warmup pass
  absorbs jit compiles, so the numbers are what a long-lived endpoint
  serves at.  Reported: total and decode-only tok/s, slot occupancy,
  and the continuous/static ratio.
* ``plan_cache/<arch>`` — the compile-side tiers on every zoo config
  (full, non-smoke): cold DSE wall, cache-hit fetch time (fresh
  :class:`PlanCache` instance, so the disk tier + static re-verify are
  on the measured path), and warm re-DSE wall/QoR seeded from the
  cached assignment snapshot.

Absolute gates (checked in ``--compare`` mode, independent of the
baseline — these are the serving path's acceptance criteria, not
regression bounds):

* continuous ≥ static total tok/s on the mixed-length trace;
* cache-hit plan fetch < 5 ms;
* warm re-DSE wall < cold wall on every config;
* warm QoR never worse than cold.

Baseline-relative gates (vs. the committed ``BENCH_serve.json``):
continuous tok/s must not drop below ``1/threshold ×`` baseline, and
warm wall / fetch time must not grow past ``threshold ×``.

Regression gate (CI)::

    PYTHONPATH=src python -m benchmarks.bench_serve \
        --compare BENCH_serve.json [--threshold 2.0] [--fast]

In compare mode fresh results go to a scratch dir (unless
``REPRO_BENCH_OUT_DIR`` is set) so a failing run cannot overwrite the
baseline it is judged against.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time
from pathlib import Path

from repro.configs import get_config, list_archs
from repro.configs.base import ShapeSpec
from repro.core import (SINGLE_POD, CachedPlan, PlanCache, PlanKey,
                        build_lm_graph, canonical_snapshot, optimize,
                        shape_bucket)

#: serving throughput arms (smoke configs — the arm measures scheduler
#: behaviour, not model FLOPs; MoE archs are static-only by design).
SERVE_ARMS = ("smollm-135m", "xlstm-125m")

#: the serving shape the plan-cache arms compile for.
DECODE_SEQ, DECODE_BATCH = 2048, 16

#: acceptance ceiling for a cache-hit plan fetch (disk tier + static
#: re-verification included).
FETCH_MS_GATE = 5.0


def _bench_serve_arm(arch: str, repeats: int = 3) -> dict:
    from repro.launch.serve import main as serve_main
    args = ["--arch", arch, "--smoke", "--slots", "4",
            "--requests", "24", "--prompt-len-range", "4", "48",
            "--gen-range", "32", "96", "--temperature", "0.0",
            "--seed", "0", "--static", "--no-plan"]
    # every pass carries --warmup: serve_main builds a fresh LM (and so
    # a fresh jit cache) per call, so an unwarmed pass would pay the
    # compiles inside its measured window.  The two paths run
    # back-to-back inside each pass, so a per-pass ratio is controlled
    # for machine-wide noise (CPU contention hits both paths of one
    # pass, not one path of one pass) — keep the best paired pass.
    runs = [serve_main(args + ["--warmup", "1"]) for i in range(repeats)]
    best = max(runs, key=lambda m: m["continuous_vs_static"])
    c, s = best["continuous"], best["static"]
    return {
        "tok_per_s": c["tok_per_s"],
        "decode_tok_per_s": c["decode_tok_per_s"],
        "static_tok_per_s": s["tok_per_s"],
        "ratio_vs_static": best["continuous_vs_static"],
        "occupancy": c["occupancy"],
        "requests": c["requests"],
        "generated": c["generated"],
    }


def _bench_plan_cache_arm(arch: str, cache_root: Path,
                          repeats: int = 2) -> dict:
    cfg = get_config(arch)
    bucket = shape_bucket("decode", DECODE_SEQ, DECODE_BATCH)
    shape = ShapeSpec(bucket, DECODE_SEQ, DECODE_BATCH, "decode")
    key = PlanKey.make(cfg, SINGLE_POD, bucket)

    # best-of-N on both walls: a single scheduler hiccup on either side
    # must not decide the warm-faster-than-cold gate.
    cold_wall = float("inf")
    for _ in range(repeats):
        g = build_lm_graph(cfg, shape)
        t0 = time.perf_counter()
        sched, plan, rep_cold = optimize(g, SINGLE_POD, training=False)
        cold_wall = min(cold_wall, time.perf_counter() - t0)

    cache = PlanCache(cache_root)
    cache.put(CachedPlan(key=key, plan=plan,
                         snapshot=canonical_snapshot(sched),
                         qor_total_s=rep_cold.cost.total_s,
                         stored_unix=time.time()))
    # fresh instance: the hit pays JSON parse + plan rebuild + static
    # re-verify, exactly what a restarted server pays.
    fresh = PlanCache(cache_root)
    t0 = time.perf_counter()
    got, vrep = fresh.fetch(key, SINGLE_POD)
    fetch_ms = (time.perf_counter() - t0) * 1e3
    assert got is not None and vrep.ok, f"{arch}: cache hit failed verify"

    warm_wall = float("inf")
    for _ in range(repeats):
        g2 = build_lm_graph(cfg, shape)
        t0 = time.perf_counter()
        _, _, rep_warm = optimize(g2, SINGLE_POD, training=False,
                                  warm_start=got.snapshot)
        warm_wall = min(warm_wall, time.perf_counter() - t0)

    return {
        "nodes": len(sched.nodes),
        "cold_wall_s": cold_wall,
        "warm_wall_s": warm_wall,
        "warm_speedup": cold_wall / warm_wall if warm_wall else float("inf"),
        "fetch_ms": fetch_ms,
        "cold_qor_s": rep_cold.cost.total_s,
        "warm_qor_s": rep_warm.cost.total_s,
        "warm_covered": rep_warm.parallelize.warm_covered,
        "warm_verify_ok": bool(rep_warm.verify.ok),
    }


def run(report, fast: bool = False) -> dict:
    results: dict[str, dict] = {}
    for arch in (SERVE_ARMS[:1] if fast else SERVE_ARMS):
        r = _bench_serve_arm(arch)
        results[f"serve/{arch}"] = r
        report.add(f"serve/{arch}", us_per_call=1e6 / r["tok_per_s"],
                   derived=f"tok_per_s={r['tok_per_s']:.0f}"
                           f"|static={r['static_tok_per_s']:.0f}"
                           f"|ratio={r['ratio_vs_static']:.2f}"
                           f"|occ={r['occupancy']:.2f}")
    archs = list_archs()
    if fast:
        archs = archs[:3]
    with tempfile.TemporaryDirectory(prefix="repro_plan_cache_") as td:
        for arch in archs:
            r = _bench_plan_cache_arm(arch, Path(td) / arch)
            results[f"plan_cache/{arch}"] = r
            report.add(f"plan_cache/{arch}",
                       us_per_call=r["warm_wall_s"] * 1e6,
                       derived=f"cold_ms={r['cold_wall_s'] * 1e3:.0f}"
                               f"|warm_ms={r['warm_wall_s'] * 1e3:.0f}"
                               f"|speedup={r['warm_speedup']:.1f}x"
                               f"|fetch_ms={r['fetch_ms']:.2f}"
                               f"|covered={r['warm_covered']}/{r['nodes']}")

    out_dir = Path(os.environ.get("REPRO_BENCH_OUT_DIR", "."))
    out = out_dir / "BENCH_serve.json"
    try:
        out.write_text(json.dumps(results, indent=2, sort_keys=True))
    except OSError as e:  # read-only CWD: keep the CSV rows, note the miss
        report.add("serve/json_write_failed", 0.0, derived=str(e))
    return results


def gate(results: dict, qor_tolerance: float = 1e-3) -> list[str]:
    """The absolute acceptance gates — hold against any baseline."""
    failures: list[str] = []
    for arm, r in sorted(results.items()):
        if arm.startswith("serve/"):
            if r["ratio_vs_static"] < 1.0:
                failures.append(
                    f"{arm}: continuous batching {r['tok_per_s']:.0f} tok/s "
                    f"< static baseline {r['static_tok_per_s']:.0f} tok/s "
                    f"({r['ratio_vs_static']:.2f}x)")
        elif arm.startswith("plan_cache/"):
            if r["fetch_ms"] >= FETCH_MS_GATE:
                failures.append(
                    f"{arm}: cache-hit fetch {r['fetch_ms']:.2f} ms "
                    f">= {FETCH_MS_GATE} ms budget")
            if r["warm_wall_s"] >= r["cold_wall_s"]:
                failures.append(
                    f"{arm}: warm re-DSE {r['warm_wall_s'] * 1e3:.0f} ms "
                    f"not faster than cold {r['cold_wall_s'] * 1e3:.0f} ms")
            if r["warm_qor_s"] > r["cold_qor_s"] * (1 + qor_tolerance):
                failures.append(
                    f"{arm}: warm QoR {r['warm_qor_s'] * 1e3:.4f} ms worse "
                    f"than cold {r['cold_qor_s'] * 1e3:.4f} ms")
            if not r["warm_verify_ok"]:
                failures.append(f"{arm}: warm-started plan failed the exit "
                                "verifier")
    return failures


def compare(results: dict, baseline: dict, threshold: float,
            allow_missing: bool = False) -> list[str]:
    """Baseline-relative regression checks + the absolute gates."""
    failures = gate(results)
    for arm in sorted(set(results) & set(baseline)):
        new, old = results[arm], baseline[arm]
        if arm.startswith("serve/"):
            ratio = (old["tok_per_s"] / new["tok_per_s"]
                     if new["tok_per_s"] else float("inf"))
            print(f"{arm}: {old['tok_per_s']:.0f} -> "
                  f"{new['tok_per_s']:.0f} tok/s")
            if ratio > threshold:
                failures.append(
                    f"{arm}: throughput dropped to {new['tok_per_s']:.0f} "
                    f"tok/s, {ratio:.2f}x below baseline "
                    f"{old['tok_per_s']:.0f} (threshold {threshold:.2f}x)")
        elif arm.startswith("plan_cache/"):
            print(f"{arm}: warm {old['warm_wall_s'] * 1e3:.0f} -> "
                  f"{new['warm_wall_s'] * 1e3:.0f} ms, fetch "
                  f"{old['fetch_ms']:.2f} -> {new['fetch_ms']:.2f} ms")
            w_ratio = (new["warm_wall_s"] / old["warm_wall_s"]
                       if old["warm_wall_s"] else float("inf"))
            # sub-50ms walls gate only on real growth, not timer noise
            if w_ratio > threshold \
                    and new["warm_wall_s"] - old["warm_wall_s"] > 0.05:
                failures.append(
                    f"{arm}: warm re-DSE wall "
                    f"{new['warm_wall_s'] * 1e3:.0f} ms is "
                    f"{w_ratio:.2f}x the baseline "
                    f"{old['warm_wall_s'] * 1e3:.0f} ms")
    missing = sorted(set(baseline) - set(results))
    if missing:
        if allow_missing:
            print(f"note: baseline arms not re-run: {missing}")
        else:
            failures.append(
                f"baseline arms not re-run: {missing} (drop --fast, or "
                f"pass --allow-missing-arms to gate on a subset)")
    return failures


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(
        description="serving-path benchmark / regression gate")
    ap.add_argument("--fast", action="store_true",
                    help="one serve arm, three plan-cache arms")
    ap.add_argument("--compare", metavar="BASELINE_JSON", default=None,
                    help="diff against a committed BENCH_serve.json and "
                         "exit nonzero on regression or gate failure")
    ap.add_argument("--threshold", type=float, default=2.0,
                    help="max allowed regression ratio vs baseline")
    ap.add_argument("--allow-missing-arms", action="store_true")
    args = ap.parse_args(argv)

    baseline = None
    if args.compare is not None:
        baseline = json.loads(Path(args.compare).read_text())
        if "REPRO_BENCH_OUT_DIR" not in os.environ:
            os.environ["REPRO_BENCH_OUT_DIR"] = tempfile.mkdtemp(
                prefix="repro_bench_")

    from .run import Report
    report = Report()
    print("name,us_per_call,derived")
    results = run(report, fast=args.fast)
    if baseline is None:
        failures = gate(results)
    else:
        failures = compare(results, baseline, args.threshold,
                           allow_missing=args.allow_missing_arms)
    for f in failures:
        print(f"FAIL: {f}", file=sys.stderr)
    if not failures:
        print("serve gate: OK", file=sys.stderr)
    return 1 if failures else 0


if __name__ == "__main__":
    raise SystemExit(main())
