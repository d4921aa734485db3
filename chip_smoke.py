#!/usr/bin/env python3
"""The system's main path once on a TPU, at the published width of
smollm-360m (32 layers, d_model 960, 15/5 heads of 64, vocab 49152),
with random weights from ``--seed``.

    python3 chip_smoke.py              # one chip: kernels, serve, train
    python3 chip_smoke.py --chips 4    # 2x2 host: plan-sharded train step

One chip, in order, in this one process (it starts no child):

1. kernels — the five Pallas kernels compiled, at the widths of the
   models that use them, against their ``ref.py`` oracles;
2. serve — ``repro.launch.serve.main`` on the full config: every request
   finishes, prefill logits and caches are finite, at least two requests
   stream exactly ``scheduler.decode_offline``'s tokens, and every other one
   stays offline-greedy up to a last-bit near-tie (the bf16 logits of a
   random-weight model tie often, and batch-8 and batch-1 steps may
   round their last bit apart);
3. train — ``repro.launch.train.main`` for a few steps: every loss is
   finite.

Every plan the phases derive must compile without a degradation and
lint clean.  ``--chips 4`` runs only one plan-sharded train step on a
(data=2, model=2) mesh and the same step on one device.

Timings printed here are smoke figures, not benchmarks.  The last line
of stdout is ``{"ok": true, "device": {...}}``; a failed phase, or a
platform other than ``tpu``, exits nonzero without it.
"""
from __future__ import annotations

import argparse
import json
import math
import shutil
import sys
import tempfile
import time
import traceback
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

ARCH = "smollm-360m"
SERVE_ARGV = ["--arch", ARCH, "--slots", "8", "--requests", "16",
              "--prompt-len-range", "16", "512", "--gen-range", "32", "128",
              "--temperature", "0"]
TRAIN_ARGV = ["--arch", ARCH, "--steps", "5", "--batch", "8",
              "--seq", "1024", "--remat", "full", "--ckpt-every", "0"]
ORACLE_REQUESTS = 16
#: A streamed token that is not the offline argmax must sit within this
#: many ulps of the logits' dtype below it (see ``greedy_margins``).
TIE_ULPS = 4


class CompileStats:
    """Backend compile seconds and persistent-cache hits/misses, from
    JAX's monitoring events."""

    def __init__(self):
        import jax
        self.compile_s, self.compiles, self.hits, self.misses = 0.0, 0, 0, 0
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _event(self, name, **_):
        if name == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif name == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def _duration(self, name, secs, **_):
        if name == "/jax/core/compile/backend_compile_duration":
            self.compile_s += secs
            self.compiles += 1

    def snapshot(self) -> tuple:
        return (self.compile_s, self.compiles, self.hits, self.misses)

    def since(self, snap: tuple) -> str:
        s, n, h, m = (a - b for a, b in zip(self.snapshot(), snap))
        return (f"{n} compiles, {s:.1f} s backend compile, "
                f"cache {h} hits / {m} misses")


def plan_problems(where: str, plan_info: dict) -> list[str]:
    """A degraded or hazardous plan fails the run: the never-fail ladder
    must not turn a broken compile into a green smoke run."""
    out = [f"{where}: plan degraded: {d}"
           for d in plan_info.get("degradations", [])]
    lint = plan_info.get("lint")
    if lint is None:
        out.append(f"{where}: plan was not linted")
    elif not lint["ok"]:
        out.append(f"{where}: plan lint: {lint['issues']}")
    return out


def _max_err(got, want, rtol, atol) -> tuple[bool, float]:
    g = np.asarray(got, np.float32)
    w = np.asarray(want, np.float32)
    ok = bool(np.all(np.abs(g - w) <= atol + rtol * np.abs(w))
              and np.isfinite(g).all())
    return ok, float(np.max(np.abs(g - w)))


def kernel_phase(cases, seed: int) -> list[str]:
    """Each kernel compiled (``interpret=None`` resolves to the compiler
    off the CPU) against its oracle, which runs at full f32 matmul
    precision so it is the reference and not a bf16 approximation."""
    import jax
    problems = []
    key = jax.random.PRNGKey(seed)
    for case in cases:
        key, sub = jax.random.split(key)
        xs = case.make(sub)
        got = jax.jit(case.run)(*xs)
        with jax.default_matmul_precision("highest"):
            want = jax.jit(case.ref)(*xs)
        ok, err = _max_err(got, want, case.rtol, case.atol)
        print(f"[smoke] kernel {case.name} ({case.arch} widths, "
              f"{[tuple(s) for s, _ in case.shapes]}): max |err| {err:.3g} "
              f"(rtol {case.rtol}, atol {case.atol}) "
              f"{'ok' if ok else 'MISMATCH'}", flush=True)
        if not ok:
            problems.append(f"kernel {case.name}: max |err| {err:.3g}")
    return problems


def serve_phase(argv: list[str], n_oracle: int) -> list[str]:
    import jax
    import jax.numpy as jnp

    from repro.launch import serve
    from repro.launch.scheduler import (Request, decode_offline,
                                         greedy_margins)

    m = serve.main(argv)
    problems = plan_problems("serve", m["plan"])
    c = m["continuous"]
    print(f"[smoke] serve (smoke figure, not a benchmark): "
          f"{c['generated']} tokens, {c['requests']} requests, "
          f"{c['tok_per_s']:.1f} tok/s, "
          f"wall {c['wall_s']:.2f} s", flush=True)

    # The oracle side: same seed → same plan, parameters and trace.
    args = serve.parse_args(argv)
    srv = serve.build(args)
    trace, outputs = srv.trace, m["outputs"]
    if len(outputs) != len(trace):
        problems.append(f"serve: {len(outputs)} of {len(trace)} requests "
                        "finished")
    for rid, (t, out) in enumerate(zip(trace, outputs)):
        if len(out) != t["max_new"]:
            problems.append(f"serve: request {rid} stopped after "
                            f"{len(out)} of {t['max_new']} tokens")

    with jax.set_mesh(srv.mesh):
        width = max(t["prompt_len"] for t in trace)
        toks = np.zeros((len(trace), width), np.int32)
        for i, t in enumerate(trace):
            toks[i, :t["prompt_len"]] = t["prompt"]
        lengths = jnp.asarray([t["prompt_len"] for t in trace], jnp.int32)
        filled = jax.jit(srv.lm.prefill)(
            srv.params, {"tokens": jnp.asarray(toks), "lengths": lengths})
        if not all(bool(jnp.isfinite(x).all())
                   for x in jax.tree.leaves(filled)):
            problems.append("serve: non-finite prefill logits or caches")

        order = sorted(range(len(trace)), key=lambda i: (
            trace[i]["prompt_len"] + trace[i]["max_new"], i))
        exact = 0
        for rid in order[:n_oracle]:
            t = trace[rid]
            req = Request(rid=rid, prompt_len=t["prompt_len"],
                          max_new=t["max_new"], prompt=t["prompt"],
                          temperature=t["temperature"])
            ref = decode_offline(srv.lm, srv.params, req, seed=args.seed,
                                 s_max=srv.s_max)
            same = ref == outputs[rid]
            line = (f"[smoke] serve request {rid} (prompt {t['prompt_len']},"
                    f" {t['max_new']} new): streamed == decode_offline: "
                    f"{same}")
            if same:
                exact += 1
                print(line, flush=True)
                continue
            first = next((j for j, (a, b) in enumerate(
                zip(ref, outputs[rid])) if a != b), None)
            worst = (float(greedy_margins(srv.lm, srv.params, req,
                                          outputs[rid],
                                          s_max=srv.s_max).max())
                     if t["temperature"] == 0 else math.inf)
            print(f"{line} (first diff at {first}; streamed tokens sit "
                  f"at most {worst:g} ulps below the offline argmax)",
                  flush=True)
            if worst > TIE_ULPS:
                problems.append(f"serve: request {rid} differs from "
                                f"decode_offline at token {first}, "
                                f"{worst:g} ulps off greedy")
        need = min(2, n_oracle)
        print(f"[smoke] serve: {exact} of {min(n_oracle, len(trace))} "
              "requests equal decode_offline token for token", flush=True)
        if exact < need:
            problems.append(f"serve: {exact} requests equal "
                            f"decode_offline, need {need}")
    return problems


def train_phase(argv: list[str]) -> list[str]:
    from repro.launch import train

    ckpt = tempfile.mkdtemp(prefix="chip_smoke_ckpt_")
    try:
        t0 = time.perf_counter()
        out = train.main(argv + ["--ckpt-dir", ckpt])
        wall = time.perf_counter() - t0
    finally:
        shutil.rmtree(ckpt, ignore_errors=True)
    problems = plan_problems("train", out["plan"])
    losses = out["losses"]
    print(f"[smoke] train losses {losses} (wall {wall:.1f} s incl. "
          "compile; smoke figure, not a benchmark)", flush=True)
    if not losses or not all(math.isfinite(x) for x in losses):
        problems.append(f"train: non-finite losses {losses}")
    return problems


def multichip_phase(seed: int, batch: int, seq: int,
                    smoke: bool = False) -> list[str]:
    """One plan-sharded train step on a (data=2, model=2) mesh, against
    the same step on one device with the same parameters and batch."""
    import jax
    import jax.numpy as jnp

    from repro.configs import get_config
    from repro.configs.base import ShapeSpec
    from repro.core import MeshSpec, analyze_plan, build_lm_graph, optimize
    from repro.data import SyntheticCorpus
    from repro.launch.hlo_analysis import collective_bytes
    from repro.launch.mesh import make_mesh
    from repro.launch.steps import build_train_step
    from repro.models.lm import LM
    from repro.optim import AdamW

    cfg = get_config(ARCH, smoke=smoke)
    opt = AdamW(moment_dtype=cfg.opt_moment_dtype)
    shape = ShapeSpec("chips4", seq, batch, "train")
    mspec = MeshSpec((("data", 2), ("model", 2)))
    _, plan, report = optimize(build_lm_graph(cfg, shape), mspec,
                               training=True)
    lint = analyze_plan(plan, mspec)
    problems = plan_problems("chips4", {
        "degradations": [str(d) for d in report.degradations],
        "lint": {"ok": lint.ok, "issues": [str(i) for i in lint.issues]}})
    rules = {k: list(v) for k, v in plan.rules.items()}
    print(f"[smoke] chips4 plan rules: {rules}", flush=True)
    for dim, size in (("heads", cfg.n_heads), ("kv_heads", cfg.n_kv_heads)):
        f = math.prod(mspec.size(a) for a in rules.get(dim, ()))
        if size % f:
            problems.append(f"chips4: {dim}={size} split {f} ways")

    devices = jax.devices()[:4]
    # Host copies: each run places its own, since the step donates them.
    params = jax.device_get(LM(cfg).init(jax.random.PRNGKey(seed))[0])
    batch_np = SyntheticCorpus(cfg.vocab, seed=seed).batch(0, 0, batch, seq)

    def run(mesh):
        with jax.set_mesh(mesh):
            step = build_train_step(cfg, shape, mesh, plan, opt=opt,
                                    remat="full")
            p = jax.device_put(params, step.in_shardings[0])
            o = jax.device_put(opt.init(params), step.in_shardings[1])
            b = jax.device_put({k: jnp.asarray(v)
                                for k, v in batch_np.items()},
                               step.in_shardings[2])
            compiled = step.fn.lower(p, o, b).compile()
            p2, _, metrics = compiled(p, o, b)
            loss = float(metrics["loss"])
            return loss, compiled.as_text(), p2

    t0 = time.perf_counter()
    loss4, hlo4, p4 = run(make_mesh((2, 2), ("data", "model"), devices))
    t4 = time.perf_counter() - t0
    coll = collective_bytes(hlo4)
    spread = {len(x.sharding.device_set) for x in jax.tree.leaves(p4)}
    split = sum(not x.sharding.is_fully_replicated
                for x in jax.tree.leaves(p4))
    del p4
    t0 = time.perf_counter()
    loss1, _, _ = run(make_mesh((1, 1), ("data", "model"), devices[:1]))
    t1 = time.perf_counter() - t0
    rel = abs(loss4 - loss1) / max(abs(loss1), 1e-9)
    print(f"[smoke] chips4 loss {loss4:.6f} on 2x2 vs {loss1:.6f} on one "
          f"device (rel diff {rel:.2e}); collectives "
          f"{coll.count_by_kind} = {coll.total_bytes} bytes; params on "
          f"{sorted(spread)} devices, {split} leaves split; wall "
          f"{t4:.1f} s / {t1:.1f} s incl. compile (smoke figure)",
          flush=True)
    if not (math.isfinite(loss4) and rel <= 2e-2):
        problems.append(f"chips4: loss {loss4} vs one device {loss1}")
    if coll.total_bytes <= 0:
        problems.append("chips4: no collectives in the compiled step")
    if spread != {4} or split == 0:
        problems.append(f"chips4: parameters not spread over the mesh "
                        f"(device sets {spread}, {split} split leaves)")
    return problems


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax
    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    print(f"[smoke] device: {device}", flush=True)
    if device["platform"] != "tpu":
        print("[smoke] no TPU: this run needs the chip", file=sys.stderr)
        return 1
    if device["count"] < args.chips:
        print(f"[smoke] --chips {args.chips} needs {args.chips} devices",
              file=sys.stderr)
        return 1

    from repro.kernels.cases import CASE_NAMES, kernel_case
    from repro.launch.compile_cache import enable_compile_cache
    print(f"[smoke] compile cache: {enable_compile_cache()}", flush=True)
    stats = CompileStats()

    if args.chips == 4:
        phases = [("chips4", lambda: multichip_phase(args.seed, 8, 1024))]
    else:
        seed = ["--seed", str(args.seed)]
        phases = [
            ("kernels", lambda: kernel_phase(
                [kernel_case(n) for n in CASE_NAMES], args.seed)),
            ("serve", lambda: serve_phase(SERVE_ARGV + seed,
                                          ORACLE_REQUESTS)),
            ("train", lambda: train_phase(TRAIN_ARGV + seed)),
        ]
    failed = []
    for name, phase in phases:
        snap, t0 = stats.snapshot(), time.perf_counter()
        try:
            problems = phase()
        except Exception:
            traceback.print_exc()
            problems = [f"{name}: raised"]
        print(f"[smoke] phase {name}: "
              f"{'FAILED' if problems else 'ok'} in "
              f"{time.perf_counter() - t0:.1f} s ({stats.since(snap)})",
              flush=True)
        for p in problems:
            print(f"[smoke]   {p}", flush=True)
        failed += problems
    if failed:
        return 1
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
