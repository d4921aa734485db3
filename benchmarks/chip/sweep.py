#!/usr/bin/env python3
"""Find an open-loop cell's knee: the highest arrival rate at which the
queue does not grow over the window.  One process builds and warms the
server once, then runs one window per rate.  A tool for choosing the
fixed rate written into a traffic file; the benchmark's runs never
call it.

    python3 benchmarks/chip/sweep.py --workload smollm-360m.chat \
        --rates 0.6 0.8 1.0 1.25 --seconds 40 --seed 5
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


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, required=True)
    args = ap.parse_args(argv)
    ctx = harness.cli_context(args.workload, args.seed, args.seconds,
                              False, T_PROCESS)
    import numpy as np

    import mix
    drv = harness.load_module("drivers", ctx.traffic["kind"])
    srv = drv.setup(ctx)
    srv.start()
    rows = []
    for rate in args.rates:
        t = dict(ctx.traffic, rate=rate)
        plan = mix.open_loop(t, args.seed, args.seconds, ctx.spec.vocab)
        w = drv.window(ctx, srv, plan, t["preroll_s"], args.seconds)
        ttft, tpot, due_in = drv.latencies(w)
        q = [n for at, n in w.queue if 0 <= at]
        third = max(len(q) // 3, 1)
        row = {"rate": rate, "due": len(due_in),
               "ttft_p50_s": float(np.percentile(ttft, 50)),
               "ttft_p90_s": float(np.percentile(ttft, 90)),
               "tpot_p50_ms": float(np.percentile(tpot, 50)) * 1e3,
               "tpot_p90_ms": float(np.percentile(tpot, 90)) * 1e3,
               "queue_first_third": float(np.mean(q[:third])),
               "queue_last_third": float(np.mean(q[-third:])),
               "queue": q, "compiles": w.compiles}
        rows.append(row)
        print(json.dumps(row), flush=True)
    srv.stop()
    out = ctx.out_dir / f"sweep_{args.seed}.json"
    out.write_text(json.dumps(rows, indent=1))
    print(f"[sweep] wrote {out}", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
