"""Serving under a traffic mix, through the program's own entry points:
``serve.fetch_plan`` (the DSE, timed), ``LM`` with the plan on the
host's mesh, and one ``ContinuousBatcher`` whose ``run`` a server
thread calls whenever requests wait.  The drivers submit requests from
the main thread with ``submit``, on their schedule.

Weights come from :mod:`weights`, made in the plan's parameter
shardings over the cell's mesh; the check of what was served runs the
family's plain reference over a sample of the finished requests.
"""
from __future__ import annotations

import threading
import time
from dataclasses import dataclass

import numpy as np

import harness
import weights

#: ``run(max_steps=...)`` evicts what is still active once its step
#: budget is spent; the server thread's budget never is.
NO_BUDGET = 1 << 62


@dataclass
class Sent:
    """A request as the load generator sent it."""
    req: object            # the program's Request
    due: float             # perf_counter time it was due
    sent: float            # perf_counter time submit() returned


class TimedTokens(list):
    """A request's output tokens that also keep the host time each one
    reached the host (the batcher appends a token once its step's logits
    are read back)."""

    def __init__(self, tokens=()):
        super().__init__(tokens)
        self.times: list[float] = []

    def append(self, tok) -> None:
        self.times.append(time.perf_counter())
        super().append(tok)


class Server:
    def __init__(self, ctx: harness.Context, *, slots: int, s_max: int):
        import jax
        from repro.core import analyze_plan
        from repro.launch.scheduler import ContinuousBatcher
        from repro.launch.serve import fetch_plan
        from repro.launch.steps import sharding_tree
        from repro.models.lm import LM

        self.ctx, self.slots, self.s_max = ctx, slots, s_max
        self.mesh, mspec = ctx.mesh, ctx.mesh_spec
        plan, info = fetch_plan(ctx.arch_cfg, slots=slots, s_max=s_max,
                                cache_root=None, mesh=mspec)
        self.plan_ms = info["fetch_ms"]
        lint = analyze_plan(plan, mspec)
        if not lint.ok or (info["report"] and info["report"].degradations):
            ctx.note(f"plan lint {lint.summary()}")
        self.lm = LM(ctx.arch_cfg, plan=plan, mesh=self.mesh, remat="none")
        abstract, dims = self.lm.init(None, True)
        self.layout = harness.family(ctx.spec.family).layout(ctx.spec)
        bad = weights.check_tree(self.layout, abstract)
        if bad:
            raise RuntimeError(f"parameter layout differs: {bad}")
        self.shardings = sharding_tree(dims, self.mesh, plan, weight=True,
                                       shapes_tree=abstract)
        with jax.set_mesh(self.mesh):
            self.params = weights.make(self.layout, ctx.seed, self.shardings)
        # The batcher's own key is used only to sample at temperature > 0.
        self.batcher = ContinuousBatcher(self.lm, self.params, slots=slots,
                                         s_max=s_max, seed=ctx.seed % 2**31)
        self.reports: list = []
        self.spans: list = []            # (start, end) of each run() call
        self._stop = threading.Event()
        self._error: BaseException | None = None
        self._busy = False
        self._thread: threading.Thread | None = None

    # -- set-up ------------------------------------------------------------
    def warm(self, buckets: list[int], widths: list[int]) -> None:
        """Compile (or fetch from the cache) and run once the decode step
        and every prefill program of ``(bucket, width)``."""
        import jax
        rng = np.random.default_rng(0)
        b = self.batcher
        vocab = self.ctx.spec.vocab
        self.warm_decode(min(buckets))
        with jax.set_mesh(self.mesh):
            for bucket in buckets:
                for k in widths:
                    for _ in range(k):
                        b.submit(rng.integers(0, vocab, bucket,
                                              dtype=np.int32), 1)
                    b.run(max_steps=NO_BUDGET)

    def warm_decode(self, bucket: int) -> None:
        """The decode step, before any other prefill: the batch cache it
        returns is laid out as every later call sees it (a fresh one is
        not, and a prefill on it compiles apart)."""
        import jax
        rng = np.random.default_rng(1)
        with jax.set_mesh(self.mesh):
            self.batcher.submit(rng.integers(
                0, self.ctx.spec.vocab, bucket, dtype=np.int32), 3)
            self.batcher.run(max_steps=NO_BUDGET)

    def reseed(self, seed: int, bucket: int) -> None:
        """Weights of another seed and a fresh batch cache; the compiled
        programs stay.  Only while the server thread is stopped."""
        import jax
        from repro.launch.scheduler import ContinuousBatcher
        self.batcher = self.params = None
        with jax.set_mesh(self.mesh):
            self.params = weights.make(self.layout, seed, self.shardings)
        self.batcher = ContinuousBatcher(self.lm, self.params,
                                         slots=self.slots, s_max=self.s_max,
                                         seed=seed % 2**31)
        self.warm_decode(bucket)

    # -- the window --------------------------------------------------------
    def start(self) -> None:
        self._stop.clear()
        self._thread = threading.Thread(target=self._serve, name="server",
                                        daemon=True)
        self._thread.start()

    def _serve(self) -> None:
        import jax
        try:
            with jax.set_mesh(self.mesh):
                while True:
                    if self.batcher.queue:
                        self._busy = True
                        t0 = time.perf_counter()
                        self.reports.append(
                            self.batcher.run(max_steps=NO_BUDGET))
                        self.spans.append((t0, time.perf_counter()))
                        self._busy = False
                    elif self._stop.is_set():
                        return
                    else:
                        time.sleep(2e-4)
        except BaseException as e:       # handed to the main thread
            self._error = e

    def submit(self, prompt: np.ndarray, max_new: int, due: float) -> Sent:
        self.raise_if_failed()
        req = self.batcher.submit(prompt, max_new)
        # The first token comes after a prefill of milliseconds at least.
        req.out = TimedTokens(req.out)
        return Sent(req, due, time.perf_counter())

    def raise_if_failed(self) -> None:
        if self._error is not None:
            raise RuntimeError("server thread failed") from self._error

    def drain(self, timeout: float) -> None:
        """Wait until the batcher has finished everything it was sent."""
        end = time.perf_counter() + timeout
        while self._busy or self.batcher.queue:
            self.raise_if_failed()
            if time.perf_counter() > end:
                raise RuntimeError(f"server did not drain in {timeout} s")
            time.sleep(1e-3)
        self.raise_if_failed()

    def stop(self) -> None:
        self._stop.set()
        self._thread.join(10)
        if self._thread.is_alive():
            raise RuntimeError("server thread did not stop")
        self.raise_if_failed()

    def queued(self) -> int:
        return len(self.batcher.queue)

    def release(self) -> None:
        """Drop the batch cache before the reference runs."""
        self.batcher = None


class Worker:
    """Runs ``fn(stop_event)`` on a thread of its own; what it raises is
    raised again by :meth:`check` and :meth:`stop`."""

    def __init__(self, fn):
        self._stop = threading.Event()
        self.error: BaseException | None = None
        self._t = threading.Thread(target=self._run, args=(fn,), daemon=True)
        self._t.start()

    def _run(self, fn) -> None:
        try:
            fn(self._stop)
        except BaseException as e:       # handed to the main thread
            self.error = e

    def check(self) -> None:
        if self.error is not None:
            raise RuntimeError("load generator failed") from self.error

    def stop(self, timeout: float = 30.0) -> None:
        self._stop.set()
        self._t.join(timeout)
        if self._t.is_alive():
            raise RuntimeError("load generator did not stop")
        self.check()


def watch_window(ctx: harness.Context, srv: Server, gen: Worker,
                 sent: list, t_open: float, t_close: float, prof=None
                 ) -> dict:
    """The main thread's part of a window: the set-up time, the traced
    stretch, and at the close (on a timer, since stopping the profiler
    blocks this thread for seconds) the tokens of every request and the
    programs compiled since the opening."""
    marks = {"setup_s": t_open - ctx.t_process}

    def wait(until: float) -> None:
        while (now := time.perf_counter()) < until:
            gen.check()
            srv.raise_if_failed()
            time.sleep(min(until - now, 0.05))

    wait(t_open)
    c_open = ctx.compiles.n

    def close() -> None:
        marks["n_close"] = snapshot(sent)
        marks["compiles"] = ctx.compiles.n - c_open

    timer = threading.Timer(max(0.0, t_close - time.perf_counter()), close)
    timer.start()
    if prof:
        trace_at, trace_end = harness.trace_span(ctx.traffic, t_open,
                                                 t_close - t_open)
        wait(trace_at)
        prof.start()
        marks["n0"], marks["t0"] = snapshot(sent), time.perf_counter()
        wait(trace_end)
        marks["t1"], marks["n1"] = time.perf_counter(), snapshot(sent)
        prof.stop()
    wait(t_close)
    timer.join()
    return marks


def buckets_for(lo: int, hi: int, minimum: int = 16) -> list[int]:
    """Every prefill bucket a prompt length in ``[lo, hi]`` can fall in."""
    from repro.launch.scheduler import prefill_bucket
    return sorted({prefill_bucket(n, minimum) for n in range(lo, hi + 1)})


def snapshot(sent: list[Sent]) -> dict[int, int]:
    """Tokens each request has produced so far."""
    return {s.req.rid: len(s.req.out) for s in sent}


def decoded_positions(sent: list[Sent], n0: dict, n1: dict
                      ) -> list[tuple[int, int]]:
    """Positions decoded between two snapshots: token ``j >= 1`` of a
    request comes from the step at position ``prompt_len + j - 1``."""
    out = []
    for s in sent:
        a = max(n0.get(s.req.rid, 0), 1)
        b = n1.get(s.req.rid, 0)
        if b > a:
            pl = s.req.prompt_len
            out.append((pl + a - 1, pl + b - 2))
    return out


def prefilled(sent: list[Sent], lo: float, hi: float) -> list[int]:
    """Prompt lengths of the requests whose prefill ended in ``[lo, hi]``."""
    return [s.req.prompt_len for s in sent if lo <= s.req.t_first <= hi]


def traced(sent: list[Sent], marks: dict) -> dict:
    """What was decoded and prefilled in the traced stretch."""
    return {"positions": decoded_positions(sent, marks["n0"], marks["n1"]),
            "prompts": prefilled(sent, marks["t0"], marks["t1"]),
            "seconds": marks["t1"] - marks["t0"]}


def prefill_spans(sent: list[Sent], lo: float, hi: float) -> float:
    """Host seconds in ``[lo, hi]`` spent in prefill side steps: each admit
    group's span runs from its ``t_admit`` to its ``t_first``."""
    from trace import union_ns
    spans = []
    for s in sent:
        r = s.req
        if r.t_admit:
            a, b = max(r.t_admit, lo), min(r.t_first or hi, hi)
            if b > a:
                spans.append((a, b))
    return union_ns(spans)


def report_sums(reports: list) -> dict:
    return {"wall_s": sum(r.wall_s for r in reports),
            "prefill_s": sum(r.prefill_s for r in reports),
            "decode_s": sum(r.decode_s for r in reports),
            "steps": sum(r.steps for r in reports)}


def sample(done: list[Sent], n: int, seed: int) -> list[Sent]:
    """``n`` finished requests drawn from the seed, the longest among
    them."""
    import mix
    if not done:
        return []
    longest = max(done, key=lambda s: (len(s.req.out), -s.req.rid))
    rest = [s for s in done if s is not longest]
    pick = mix.rng_of(seed, 9).permutation(len(rest))[:max(n - 1, 0)]
    return [longest] + [rest[i] for i in sorted(pick)]


def token_gaps(spec, w, picked: list[Sent], s_pad: int, quant=None
               ) -> tuple[float, int]:
    """Widest gap, over every served token of ``picked``, between the
    reference's best logit and the logit of the token served; with
    ``quant``, of the token that the reference at that precision puts
    first instead (the control).  Returns (gap, tokens compared)."""
    import jax
    import jax.numpy as jnp
    fam = harness.family(spec.family)

    @jax.jit
    def gaps(w, toks, served):
        ref = fam.logits(spec, w, toks)[0]
        top = jnp.max(ref, axis=-1)
        if quant is None:
            pick = served
        else:
            pick = jnp.argmax(fam.logits(spec, w, toks, quant)[0], -1)
        return top - jnp.take_along_axis(ref, pick[:, None], -1)[:, 0]

    widest, n = 0.0, 0
    for s in picked:
        r = s.req
        seq = np.concatenate([r.prompt, np.asarray(r.out, np.int32)])
        toks = np.zeros((1, s_pad), np.int32)
        served = np.zeros((s_pad,), np.int32)
        toks[0, :len(seq) - 1] = seq[:-1]
        served[:len(seq) - 1] = seq[1:]
        g = np.asarray(gaps(w, jnp.asarray(toks), jnp.asarray(served)))
        g = g[r.prompt_len - 1:len(seq) - 1]
        widest, n = max(widest, float(g.max())), n + len(g)
    return widest, n
