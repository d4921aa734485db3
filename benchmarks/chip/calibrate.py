#!/usr/bin/env python3
"""Readings that a cell's limits are set from, on the chip at the cell's
own size, in one process: for each seed, what the program gives (the
lower reading) and what the control gives (the upper reading), and for
training what each planted fault gives.  The benchmark's runs never
call it.

    python3 benchmarks/chip/calibrate.py --workload smollm-360m.chat \
        --seconds 20 --seeds 3 4 5

Serving: a short window at the cell's load; the program's widest
served-token gap, and the control's (the reference with every matmul
operand in float8_e4m3fn) over the same prompts and tokens.
Training: the first steps of the program against the reference, the
control against the reference, and the reference on half of each batch
(a step that leaves half the batch out) against the reference.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parent))

import harness  # noqa: E402

CONTROL = "fp8"


def serve_readings(ctx, drv, seeds, seconds) -> list[dict]:
    import mix
    import serving
    t = ctx.traffic
    srv = drv.setup(ctx)
    bucket = min(serving.buckets_for(t["prompt"]["min"], t["prompt"]["max"]))
    rows = []
    for i, seed in enumerate(seeds):
        if i:
            srv.reseed(seed, bucket)
        srv.start()
        if t["kind"] == "open_loop":
            plan = mix.open_loop(t, seed, seconds, ctx.spec.vocab)
            w = drv.window(ctx, srv, plan, t["preroll_s"], seconds)
        else:
            w = drv.window(ctx, srv, seed, seconds)
        srv.stop()
        done = [s for s in w.sent if s.req.finish == "length"]
        picked = serving.sample(done, t["check_requests"], seed)
        prog, n = serving.token_gaps(ctx.spec, srv.params, picked, srv.s_max)
        ctrl, _ = serving.token_gaps(ctx.spec, srv.params, picked,
                                     srv.s_max, quant=CONTROL)
        row = {"seed": seed, "requests": len(picked), "tokens": n,
               "served_token_gap": {"program": prog, "control": ctrl}}
        rows.append(row)
        print(json.dumps(row), flush=True)
    return rows


def train_readings(ctx, drv, seeds) -> list[dict]:
    """The first steps only: no window is needed for these readings."""
    t = ctx.traffic
    built = drv.build(ctx)
    rows = []
    for seed in seeds:
        ctx.seed = seed
        job = drv.Job(ctx, built)
        fed, losses, g, d = job.first_steps()
        job.close()
        ref = drv.reference_steps(ctx.spec, t, seed, fed, built)
        ctrl = drv.reference_steps(ctx.spec, t, seed, fed, built,
                                   quant=CONTROL)
        half = [{k: v[:len(v) // 2] for k, v in h.items()} for h in fed]
        fault = drv.reference_steps(ctx.spec, t, seed, half, built)
        row = {"seed": seed,
               "program": drv.compare(losses, g, d, *ref),
               "control": drv.compare(*ctrl, *ref),
               "half_batch": drv.compare(*fault, *ref)}
        rows.append(row)
        print(json.dumps(row), flush=True)
    return rows


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    args = ap.parse_args(argv)
    ctx = harness.cli_context(args.workload, args.seeds[0], args.seconds,
                              False, T_PROCESS)
    drv = harness.load_module("drivers", ctx.traffic["kind"])
    if ctx.traffic["kind"] == "train_steps":
        rows = train_readings(ctx, drv, args.seeds)
    else:
        rows = serve_readings(ctx, drv, args.seeds, args.seconds)
    out = ctx.out_dir / f"calibrate_{args.seeds[0]}.json"
    out.write_text(json.dumps(rows, indent=1))
    print(f"[calibrate] wrote {out}", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
