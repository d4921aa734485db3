"""Backlog serving: a batch job that keeps one request waiting in the
server's queue, so a slot that frees is filled at once.

Time line: set-up (plan, weights, each prefill bucket at width 1 and
the decode step warmed), the job starts and every slot fills, then the
window of ``--seconds``.  Tokens produced in the window count; the job
stops feeding when it closes and the server drains.  Then a sample of
the finished requests is checked against the reference.

With one request queued at a time, every admit is a prefill group of
width 1, so only those programs are warmed and their zero caches held.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field

import harness
import mix
import serving


def setup(ctx: harness.Context) -> serving.Server:
    t = ctx.traffic
    p_max, o_max = mix.max_positions(t)
    from repro.launch.scheduler import prefill_bucket
    srv = serving.Server(ctx, slots=t["slots"],
                         s_max=prefill_bucket(p_max) + o_max)
    srv.warm(serving.buckets_for(t["prompt"]["min"], p_max), [1])
    return srv


@dataclass
class Window:
    t_open: float
    t_close: float
    sent: list
    n_open: dict
    n_close: dict
    compiles: int
    marks: dict = field(default_factory=dict)

    @property
    def tokens(self) -> int:
        return sum(n - self.n_open.get(k, 0) for k, n in self.n_close.items())


def window(ctx: harness.Context, srv: serving.Server, seed: int,
           seconds: float, prof=None) -> Window:
    """Feed the job until every slot is busy, then for ``seconds``; returns
    once the server has drained."""
    t = ctx.traffic
    sent: list = []
    deadline = [float("inf")]        # the window's close, once it opens

    def feed(stop):
        """Keep one request waiting, on a thread of its own: the
        profiler's calls block the main thread for seconds."""
        block, pool = 0, []
        while not stop.is_set() and time.perf_counter() < deadline[0]:
            if not srv.queued():
                if not pool:
                    pool = mix.requests(t, mix.STRATUM, seed,
                                        ctx.spec.vocab, block)
                    block += 1
                r = pool.pop(0)
                sent.append(srv.submit(r["prompt"], r["max_new"],
                                       time.perf_counter()))
            time.sleep(5e-4)

    gen = serving.Worker(feed)
    ramp_end = time.perf_counter() + t["ramp_timeout_s"]
    while sum(1 for s in list(sent) if s.req.t_first and not s.req.t_done) \
            < t["slots"]:
        gen.check()
        srv.raise_if_failed()
        if time.perf_counter() > ramp_end:
            raise RuntimeError("slots did not fill before the window")
        time.sleep(1e-3)
    t_open = time.perf_counter()
    deadline[0] = t_open + seconds
    n_open = serving.snapshot(sent)
    marks = serving.watch_window(ctx, srv, gen, sent, t_open,
                                 t_open + seconds, prof)
    gen.stop()
    compiles = marks.pop("compiles")
    n_close = marks.pop("n_close")
    srv.drain(timeout=t["drain_timeout_s"])
    return Window(t_open, t_open + seconds, sent, n_open, n_close, compiles,
                  marks)


def run(ctx: harness.Context) -> harness.Outcome:
    t = ctx.traffic
    srv = setup(ctx)
    prof = harness.Profile(ctx.out_dir / "trace") if ctx.trace else None
    srv.start()
    w = window(ctx, srv, ctx.seed, ctx.seconds, prof)
    srv.stop()
    mem = harness.memory_peak(ctx.chips)

    e2e = {"serve_tok_s": w.tokens / ctx.seconds}
    ctx.note(f"window: {w.tokens} tokens, serve_tok_s "
             f"{e2e['serve_tok_s']:.6g}, {len(w.sent)} requests sent")
    facts = {"window_s": ctx.seconds, "plan_ms": srv.plan_ms,
             "memory_peak_bytes": mem,
             "prefill_s": serving.prefill_spans(w.sent, w.t_open, w.t_close),
             "run": serving.report_sums(srv.reports),
             "serve_tok_s": e2e["serve_tok_s"], "spec": ctx.spec}
    if prof:
        facts["trace"] = prof.summary(srv.spans)
        facts["traced"] = serving.traced(w.sent, w.marks)

    in_window = [s for s in w.sent
                 if w.n_close.get(s.req.rid, 0) > w.n_open.get(s.req.rid, 0)]
    done = [s for s in w.sent if s.req.finish == "length"]
    failed = sum(1 for s in in_window if s.req.finish != "length")
    picked = serving.sample(done, t["check_requests"], ctx.seed)
    srv.release()
    gap, n_tok = serving.token_gaps(ctx.spec, srv.params, picked, srv.s_max)
    ctx.note(f"checked {len(picked)} requests, {n_tok} served tokens")
    return harness.Outcome(
        e2e=e2e, facts=facts, attempted=len(in_window), failed=failed,
        checks=[["served_token_gap", gap, ctx.limits["served_token_gap"]]],
        setup_s=w.marks["setup_s"], compiles_in_window=w.compiles)
