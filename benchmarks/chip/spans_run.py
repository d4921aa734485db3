#!/usr/bin/env python3
"""A traced run of a cell (``run.py --trace 1``) that also reads what the
program names on the trace, with :mod:`spans`.  The benchmark's runs
never call it.

    python3 benchmarks/chip/spans_run.py --workload stablelm-3b.reasoning \
        --seed 7 --seconds 50

It prints ``run.py``'s traced result line, with the metrics of
:data:`SPAN_METRICS` added in their cells and a ``spans`` entry: the idle
gaps labelled by the innermost program span, the device seconds per
(program, scope) with the share each program's scopes cover, the offset
between the batcher's ``serve.run`` spans and the harness's host-clock
mapping of the same calls, and the host cost of one span with the
profiler off and on.  The optimised HLO the scopes were joined through
is written beside the line, to ``.bench_out/<cell>/hlo/``.

The HLO comes from ``lower(...).compile().as_text()`` on the programs the
run called: a serving cell's decode step (``jit_decode_step``) and prefill
programs (``jit_prefill``), a training cell's train step
(``jit_train_step``, with the argument shapes of its first call).
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import re  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parent))

import harness  # noqa: E402
import spans  # noqa: E402
import trace  # noqa: E402

#: The per-layer metrics that read the spans and scopes, as
#: ``BENCHMARK.json`` entries.
SPAN_METRICS = [
    {"name": "host_step_ms.chat", "unit": "ms", "better": "lower",
     "source": "program_span", "layer": "scheduler", "moves": "tpot_p90_ms",
     "workloads": ["smollm-360m.chat"]},
    {"name": "gate_share.reasoning", "unit": "%", "better": "lower",
     "source": "device_trace", "layer": "model step",
     "moves": "serve_tok_s", "workloads": ["stablelm-3b.reasoning"]},
    {"name": "attn_share.train", "unit": "%", "better": "lower",
     "source": "device_trace", "layer": "train step",
     "moves": "train_tok_s", "workloads": ["smollm-360m.train_2k"]},
]

_SERVERS: list = []
_STEPS: list = []
_PROFILES: list = []


def _abstract(x):
    import jax
    if isinstance(x, jax.Array):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=x.sharding)
    return x


class Recorded:
    """A jitted function that keeps its first call's argument shapes, so
    that it can be lowered again for its optimised HLO."""

    def __init__(self, fn):
        self.fn, self.args = fn, None

    def __call__(self, *args):
        if self.args is None:
            import jax
            self.args = jax.tree.map(_abstract, args)
        return self.fn(*args)

    def hlo(self) -> str:
        return self.fn.lower(*self.args).compile().as_text()


def scope_names() -> tuple:
    """The program's scope names; none where it names none."""
    try:
        from repro.models.lm import SCOPES
    except ImportError:
        return ()
    return SCOPES


def _prefill_hlo(b) -> list[str]:
    """The optimised HLO of every prefill program the batcher compiled,
    one at a time (each is dropped before the next is loaded); one that
    the device has no room to load again is left out, and said so."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.launch.scheduler import _jit_cache
    out = []
    for key in list(_jit_cache(b.lm)):
        if key[0] != "prefill":
            continue
        _, bucket, k = key
        i32 = lambda *shape: jnp.zeros(shape, np.int32)  # noqa: E731
        try:
            out.append(b._prefill_fn(bucket, k).lower(
                b.params, i32(bucket, k, 1), i32(k), b.caches, i32(k),
                b._zero_cache(k), None).compile().as_text())
        except jax.errors.JaxRuntimeError as e:
            print(f"[spans] prefill {key[1:]} HLO not read: {e}"[:300],
                  file=sys.stderr)
    return out


def hlo_texts() -> dict[str, list[str]]:
    """Program name -> optimised HLO texts, for the programs the run
    called whose arguments are known: a server's decode step and prefill
    programs (token prompts), a train step."""
    import jax
    texts = []
    for srv in _SERVERS:
        b = srv.batcher
        with jax.set_mesh(srv.mesh):
            texts.append(b._step.lower(srv.params, b._decode_batch(),
                                       b.caches).compile().as_text())
            texts += _prefill_hlo(b)
    texts += [step.hlo() for step in _STEPS if step.args is not None]
    out: dict[str, list[str]] = {}
    for t in texts:
        out.setdefault(re.match(r"HloModule ([\w.\-]+)", t).group(1),
                       []).append(t)
    return out


def clock_offsets_ms(events, mark, t_mark: float) -> dict[str, list]:
    """The harness maps ``perf_counter`` onto the trace's clock at the
    window mark.  For spans whose ends the host clock also took (each
    ``run()`` call's end in ``Server.spans``; a prefill's wait, which
    ends where its requests' ``t_first`` is taken), the mapped host time
    minus the span's end on the trace, in ms."""
    host = {"serve.run": [e for srv in _SERVERS for _, e in srv.spans],
            "serve.prefill.wait": [r.t_first for srv in _SERVERS
                                   for rep in srv.reports
                                   for r in rep.requests if r.t_first]}
    out = {}
    for name, times in host.items():
        mapped = sorted(mark.start_ns + (t - t_mark) * 1e9 for t in times)
        ends = [e.end_ns for e in events if e.name == name]
        out[name] = [(min(mapped, key=lambda x: abs(x - end)) - end) * 1e-6
                     for end in ends if mapped]
    return out


def gap_context(events, mark, gap) -> list:
    """Host events that overlap an idle gap ``[label, s, offset_s]``:
    ``[name, thread, start, end]`` in s from the window's start."""
    s = mark.start_ns + gap[2] * 1e9
    e = s + gap[1] * 1e9
    return [[ev.label, ev.line, (ev.start_ns - mark.start_ns) * 1e-9,
             (ev.end_ns - mark.start_ns) * 1e-9] for ev in events
            if not ev.plane.startswith(trace.DEVICE_PREFIX)
            and ev.start_ns < e and ev.end_ns > s][:20]


class SpanProfile(harness.Profile):
    """The harness's profile, which also reads the spans and scopes and
    checks the host clock's mapping onto the trace's."""

    def __init__(self, log_dir):
        super().__init__(log_dir)
        self.spans = self.hlo = None
        self.clock_ms: dict = {}
        self.gap_context: list = []
        _PROFILES.append(self)

    def summary(self, work=None):
        events = spans.read(trace.find_trace(str(self.log_dir)))
        out = super().summary(work)         # reads again, then deletes
        (m,) = [e for e in events if e.name == self.MARK]
        rest = [e for e in events if e.name != self.MARK]
        self.hlo = hlo_texts()
        self.spans = spans.summarize(rest, m.start_ns, m.end_ns, self.hlo,
                                     scope_names())
        self.clock_ms = clock_offsets_ms(rest, m, self._t_mark)
        if self.spans.idle_gaps:
            self.gap_context = gap_context(rest, m, self.spans.idle_gaps[0])
        return out


def span_cost_us(log_dir: Path, n: int = 20000) -> dict[str, float]:
    """Host microseconds per span with two args, the profiler off and
    on."""
    import shutil

    import jax
    from jax.profiler import TraceAnnotation

    def per_span() -> float:
        t = time.perf_counter()
        for _ in range(n):
            with TraceAnnotation("serve.admit", bucket=256, width=1):
                pass
        return (time.perf_counter() - t) / n * 1e6

    off = per_span()
    jax.profiler.start_trace(str(log_dir))
    on = per_span()
    jax.profiler.stop_trace()
    shutil.rmtree(log_dir, ignore_errors=True)
    return {"off": off, "on": on}


def install(put=setattr) -> None:
    """Route the drivers' profile, servers and train steps through the
    recorders above (``put``: how to set a module's attribute)."""
    import serving
    drv = harness.load_module("drivers", "train_steps")

    class Server(serving.Server):
        def __init__(self, *a, **kw):
            super().__init__(*a, **kw)
            _SERVERS.append(self)

    real_build = drv.build

    def build(ctx):
        import dataclasses
        b = real_build(ctx)
        _STEPS.append(Recorded(b.step_fn))
        return dataclasses.replace(b, step_fn=_STEPS[-1])

    put(serving, "Server", Server)
    put(drv, "build", build)
    put(harness, "Profile", SpanProfile)


def span_facts(prof: SpanProfile) -> dict:
    s = prof.spans
    progs = {p: {"scopes_s": v, "covered": s.covered(p),
                 "unscoped_top": s.unscoped[p]}
             for p, v in s.scopes.items()}
    clock = {name: {"n": len(v),
                    "median": statistics.median(v) if v else None,
                    "min": min(v, default=None), "max": max(v, default=None)}
             for name, v in prof.clock_ms.items()}
    return {"idle_gaps": s.idle_gaps, "longest_gap_host": prof.gap_context,
            "programs": progs, "decode_steps": s.decode_steps,
            "clock_offset_ms": clock}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    try:
        ctx = harness.cli_context(args.workload, args.seed, args.seconds,
                                  True, T_PROCESS)
    except (harness.NoDevice, harness.ConfigMismatch) as e:
        print(f"[spans] {e}: no result", file=sys.stderr)
        return 3
    install()
    out = harness.load_module("drivers", ctx.traffic["kind"]).run(ctx)
    device = dict(ctx.device, memory_peak_bytes=out.facts["memory_peak_bytes"])
    line = harness.result_line(harness.benchmark(), args.workload, out,
                               device, True, out.facts.get("trace"))
    prof = _PROFILES[-1]
    facts = dict(out.facts, device=device, spans=prof.spans)
    for m in SPAN_METRICS:
        if args.workload in m["workloads"]:
            v = harness.load_module("metrics", m["name"]).read(facts)
            if v is not None:
                line["metrics"][m["name"]] = {"value": v, "unit": m["unit"]}
    line["spans"] = dict(span_facts(prof),
                         span_cost_us=span_cost_us(ctx.out_dir / "cost"))
    hlo_dir = ctx.out_dir / "hlo"
    hlo_dir.mkdir(exist_ok=True)
    for name, texts in (prof.hlo or {}).items():
        for i, text in enumerate(texts):
            (hlo_dir / f"{name}.{i}.txt").write_text(text)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
