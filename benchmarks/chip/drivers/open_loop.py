"""Open-loop serving: requests arrive on a Poisson schedule at the mix's
fixed ``rate``, whatever the server is doing, and each is timed from
when it was due.

Time line: set-up (plan, weights, every prefill ``(bucket, width)`` and
the decode step warmed), arrivals start, ``preroll_s`` of them fill the
system, then the window of ``--seconds``.  Requests due in the window
are the sample of the end-to-end metrics; arrivals stop when it closes
and the server drains.  Then a sample of the finished requests is
checked against the reference.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field

import numpy as np

import harness
import mix
import serving


def setup(ctx: harness.Context) -> serving.Server:
    t = ctx.traffic
    p_max, o_max = mix.max_positions(t)
    from repro.launch.scheduler import prefill_bucket
    srv = serving.Server(ctx, slots=t["slots"],
                         s_max=prefill_bucket(p_max) + o_max)
    srv.warm(serving.buckets_for(t["prompt"]["min"], p_max),
             list(range(1, t["slots"] + 1)))
    return srv


@dataclass
class Window:
    t_open: float
    t_close: float
    sent: list
    compiles: int
    queue: list                  # (time, requests waiting) samples
    marks: dict = field(default_factory=dict)   # traced stretch


def window(ctx: harness.Context, srv: serving.Server, plan: list[dict],
           preroll: float, seconds: float, prof=None) -> Window:
    """Send ``plan`` on its schedule; the window is ``[preroll,
    preroll + seconds)`` after the first arrival.  Returns once the
    server has drained."""
    t = ctx.traffic
    t_base = time.perf_counter() + 0.05
    t_open = t_base + preroll
    t_close = t_open + seconds
    sent, queue = [], []

    def feed(stop):
        """The load generator, on a thread of its own: the profiler's
        calls block the main thread for seconds."""
        next_q = t_base
        for r in plan:
            due = t_base + r["due"]
            while (now := time.perf_counter()) < due and not stop.is_set():
                if now >= next_q:
                    queue.append((now - t_open, srv.queued()))
                    next_q += 1.0
                time.sleep(max(0.0, min(due, next_q) - now))
            if stop.is_set() or due >= t_close:
                return
            sent.append(srv.submit(r["prompt"], r["max_new"], due))

    gen = serving.Worker(feed)
    marks = serving.watch_window(ctx, srv, gen, sent, t_open, t_close, prof)
    gen.stop()
    compiles = marks.pop("compiles")
    marks.pop("n_close")
    srv.drain(timeout=t["drain_timeout_s"])
    return Window(t_open, t_close, sent, compiles, queue, marks)


def latencies(w: Window) -> tuple[list[float], list[float], list]:
    """TTFT (s) of every request due in the window, and TPOT (s): every
    gap between two tokens of those requests that closed by the window's
    close, and for a request still decoding then, the open gap from its
    last token to the close.  A request with no first token by the close
    counts at its age then."""
    due_in = [s for s in w.sent if w.t_open <= s.due < w.t_close]
    ttft, tpot = [], []
    for s in due_in:
        r = s.req
        got = r.t_first if 0 < r.t_first <= w.t_close else w.t_close
        ttft.append(got - s.due)
        times = [t for t in r.out.times if t <= w.t_close]
        tpot += list(np.diff(times))
        if times and not 0 < r.t_done <= w.t_close:
            tpot.append(w.t_close - times[-1])
    return ttft, tpot, due_in


def run(ctx: harness.Context) -> harness.Outcome:
    t = ctx.traffic
    srv = setup(ctx)
    plan = mix.open_loop(t, ctx.seed, ctx.seconds, ctx.spec.vocab)
    prof = harness.Profile(ctx.out_dir / "trace") if ctx.trace else None
    srv.start()
    w = window(ctx, srv, plan, t["preroll_s"], ctx.seconds, prof)
    srv.stop()
    mem = harness.memory_peak(ctx.chips)

    ttft, tpot, due_in = latencies(w)
    late = [s.sent - s.due for s in w.sent]
    ctx.note(f"{len(due_in)} requests due in the window; generator "
             f"lateness p50 {np.percentile(late, 50) * 1e3:.3f} ms max "
             f"{max(late) * 1e3:.3f} ms; {len(plan) - len(w.sent)} not sent;"
             f" queue at close {w.queue[-1][1] if w.queue else 0}")
    e2e = {"ttft_p90_s": float(np.percentile(ttft, 90)),
           "tpot_p90_ms": float(np.percentile(tpot, 90)) * 1e3}
    ctx.note(f"window: {len(tpot)} token gaps; ttft p50 "
             f"{np.percentile(ttft, 50):.6g} s p90 "
             f"{e2e['ttft_p90_s']:.6g} s; tpot p50 "
             f"{np.percentile(tpot, 50) * 1e3:.6g} ms p90 "
             f"{e2e['tpot_p90_ms']:.6g} ms")

    facts = {"window_s": ctx.seconds, "plan_ms": srv.plan_ms,
             "memory_peak_bytes": mem,
             "prefill_s": serving.prefill_spans(w.sent, w.t_open, w.t_close),
             "run": serving.report_sums(srv.reports), "spec": ctx.spec}
    if prof:
        facts["trace"] = prof.summary(srv.spans)
        facts["traced"] = serving.traced(w.sent, w.marks)

    done = [s for s in w.sent if s.req.finish == "length"]
    failed = sum(1 for s in due_in if s.req.finish != "length")
    picked = serving.sample(done, t["check_requests"], ctx.seed)
    srv.release()
    gap, n_tok = serving.token_gaps(ctx.spec, srv.params, picked, srv.s_max)
    ctx.note(f"checked {len(picked)} requests, {n_tok} served tokens")
    return harness.Outcome(
        e2e=e2e, facts=facts, attempted=len(due_in), failed=failed,
        checks=[["served_token_gap", gap, ctx.limits["served_token_gap"]]],
        setup_s=w.marks["setup_s"], compiles_in_window=w.compiles)
