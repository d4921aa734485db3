#!/usr/bin/env python3
"""One run of one benchmark cell, on the chips of this machine.

    python3 benchmarks/chip/run.py --workload smollm-360m.chat \
        --seed 7 --seconds 50 --trace 0

Makes the weights and the traffic from ``--seed``, warms up every
program the cell's traffic can reach (set-up), measures for
``--seconds``, checks what the timed path produced against the plain
reference, and prints one JSON line last on standard output.  With
``--trace 0`` its metrics are the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics, read from a profiled stretch of
the window.  Off a TPU, or short of chips, it exits 3 and prints no
result.
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


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse_args(argv)
    try:
        ctx = harness.cli_context(args.workload, args.seed, args.seconds,
                                  bool(args.trace), T_PROCESS)
    except (harness.NoDevice, harness.ConfigMismatch) as e:
        print(f"[bench] {e}: no result", file=sys.stderr)
        return 3
    driver = harness.load_module("drivers", ctx.traffic["kind"])
    out = driver.run(ctx)
    print(f"[bench] compiles in window: {out.compiles_in_window}",
          file=sys.stderr, flush=True)
    device = dict(ctx.device, memory_peak_bytes=out.facts["memory_peak_bytes"])
    line = harness.result_line(harness.benchmark(), args.workload, out,
                               device, bool(args.trace),
                               out.facts.get("trace"))
    for name, c in line["checks"].items():
        print(f"[check] {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    print(f"[check] correct {line['correct']} attempted "
          f"{line['attempted']} failed {line['failed']}", file=sys.stderr,
          flush=True)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
