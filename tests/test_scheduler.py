"""Continuous-batching scheduler invariants.

The load-bearing contract is **row independence**: a request's token
stream must be byte-identical whether it is decoded alone
(:func:`decode_offline` — scalar cache positions, batch 1, no padding,
no gating) or streamed through the batcher (vector positions, per-slot
scatter writes, admit/evict churn, arbitrary co-tenants).  Everything
the serving path does — slot reuse, shape-bucketed batched prefill,
active-slot gating, per-request RNG streams — is only legal because
this equality holds.

Also pinned here: EOS/budget eviction, slot reuse beyond the batch
width, determinism in the seed, per-request RNG stream independence,
the MoE refusal, and the serve driver's metrics plumbing.
"""
import numpy as np
import pytest

jax = pytest.importorskip("jax")

from repro.configs import get_config                              # noqa: E402
from repro.launch.scheduler import (ContinuousBatcher, Request,   # noqa: E402
                                    decode_offline, greedy_margins,
                                    prefill_bucket, run_static)

S_MAX = 96


@pytest.fixture(scope="module")
def served():
    cfg = get_config("smollm-135m", smoke=True)
    from repro.models.lm import LM
    lm = LM(cfg, remat="none")
    params, _ = lm.init(jax.random.PRNGKey(0))
    return cfg, lm, params


def _trace(cfg, n=6, seed=0):
    rng = np.random.default_rng(seed)
    out = []
    for i in range(n):
        pl = int(rng.integers(3, 14))
        gen = int(rng.integers(4, 12))
        temp = 0.0 if i % 2 else 0.7
        prompt = rng.integers(0, cfg.vocab, pl).astype(np.int32)
        out.append((prompt, gen, temp))
    return out


def _run(cfg, lm, params, trace, *, slots=3, seed=0, eos_id=None,
         max_steps=None):
    b = ContinuousBatcher(lm, params, slots=slots, s_max=S_MAX, seed=seed,
                          eos_id=eos_id)
    for prompt, gen, temp in trace:
        b.submit(prompt, gen, temperature=temp)
    rep = b.run(max_steps=max_steps)
    return rep


def test_prefill_bucket():
    assert prefill_bucket(1) == 16
    assert prefill_bucket(16) == 16
    assert prefill_bucket(17) == 32
    assert prefill_bucket(33, minimum=8) == 64


def test_streamed_tokens_match_offline(served):
    """The headline invariant: admit/evict streaming == per-request
    offline decode, token for token, greedy and sampled alike."""
    cfg, lm, params = served
    rep = _run(cfg, lm, params, _trace(cfg))
    assert len(rep.requests) == 6
    for r in rep.requests:
        assert r.finish == "length" and len(r.out) == r.max_new
        ref = decode_offline(lm, params, r, seed=0, s_max=S_MAX)
        assert r.out == ref, f"rid {r.rid}: {r.out} != {ref}"


def test_greedy_margins_zero_on_greedy_stream(served):
    """The near-tie measure behind the chip smoke check: an offline greedy
    stream sits 0 ulps below every argmax; a swapped token does not."""
    cfg, lm, params = served
    prompt, gen, _ = _trace(cfg)[1]
    req = Request(rid=1, prompt_len=len(prompt), max_new=gen, prompt=prompt)
    toks = decode_offline(lm, params, req, seed=0, s_max=S_MAX)
    m = greedy_margins(lm, params, req, toks, s_max=S_MAX)
    assert m.shape == (gen,) and not m.any()
    bad = list(toks)
    bad[2] = (bad[2] + 1) % cfg.vocab
    m = greedy_margins(lm, params, req, bad, s_max=S_MAX)
    assert m[2] > 0 and not m[:2].any()


def test_slot_reuse_and_occupancy(served):
    cfg, lm, params = served
    trace = _trace(cfg, n=7)
    rep = _run(cfg, lm, params, trace, slots=2)
    assert len(rep.requests) == 7          # 7 requests through 2 slots
    assert 0.0 < rep.occupancy <= 1.0
    assert rep.generated == sum(gen for _, gen, _ in trace)
    d = rep.to_dict()
    assert d["tok_per_s"] > 0
    for r in rep.requests:
        assert r.t_admit <= r.t_first <= r.t_done


SERVE_SPANS = {"serve.run", "serve.admit", "serve.prefill.wait",
               "serve.sample", "serve.decode.dispatch", "serve.decode.wait"}


def test_run_emits_every_serve_span(served, tmp_path):
    """A profiled ``run()`` on the CPU names its host work with every
    ``serve.*`` span, each admit group with its bucket and width, on the
    clock of the profiler's other events."""
    from jax.profiler import ProfileData
    cfg, lm, params = served
    _run(cfg, lm, params, _trace(cfg, n=3))            # compiles outside
    jax.profiler.start_trace(str(tmp_path))
    rep = _run(cfg, lm, params, _trace(cfg, n=3), slots=2)
    jax.profiler.stop_trace()
    (path,) = tmp_path.glob("**/*.xplane.pb")
    events = [ev for plane in ProfileData.from_file(str(path)).planes
              for line in plane.lines for ev in line.events
              if ev.name.startswith("serve.")]
    assert {ev.name for ev in events} == SERVE_SPANS
    (run,) = [ev for ev in events if ev.name == "serve.run"]
    assert all(run.start_ns <= ev.start_ns and
               ev.start_ns + ev.duration_ns <= run.start_ns + run.duration_ns
               for ev in events)
    admits = [dict(ev.stats) for ev in events if ev.name == "serve.admit"]
    assert sum(a["width"] for a in admits) == len(rep.requests)
    assert all(a["bucket"] == prefill_bucket(1) for a in admits)
    steps = [ev for ev in events if ev.name == "serve.decode.dispatch"]
    assert len(steps) == rep.steps


def test_eos_evicts_early(served):
    cfg, lm, params = served
    base = _run(cfg, lm, params, _trace(cfg))
    # pick a token the longest request actually emits mid-stream and
    # replay with it as EOS: the stream must cut exactly there.
    victim = max(base.requests, key=lambda r: len(r.out))
    eos = victim.out[1]
    rep = _run(cfg, lm, params, _trace(cfg), eos_id=eos)
    for r in rep.requests:
        ref = decode_offline(lm, params, r, seed=0, s_max=S_MAX,
                             eos_id=eos)
        assert r.out == ref
        if eos in r.out:
            assert r.out.index(eos) == len(r.out) - 1   # stops at EOS
            assert r.finish in ("eos", "length")


def test_budget_eviction_terminates(served):
    cfg, lm, params = served
    rep = _run(cfg, lm, params, _trace(cfg), max_steps=3)
    assert rep.steps <= 3
    assert any(r.finish == "budget" for r in rep.requests)


def test_deterministic_in_seed(served):
    cfg, lm, params = served
    a = _run(cfg, lm, params, _trace(cfg), seed=7)
    b = _run(cfg, lm, params, _trace(cfg), seed=7)
    assert [r.out for r in a.requests] == [r.out for r in b.requests]
    c = _run(cfg, lm, params, _trace(cfg), seed=8)
    sampled = [r for r in c.requests if r.temperature > 0]
    assert [r.out for r in sampled] != \
        [r.out for r in a.requests if r.temperature > 0]


def test_request_streams_independent(served):
    """Sampling draws are keyed per (request, position): the same
    request decodes identically with different co-tenants."""
    cfg, lm, params = served
    full = _run(cfg, lm, params, _trace(cfg))
    solo_trace = _trace(cfg)[:1]
    solo = _run(cfg, lm, params, solo_trace, slots=1)
    assert solo.requests[0].out == full.requests[0].out


def test_moe_configs_refused():
    cfg = get_config("jamba-v0.1-52b", smoke=True)
    from repro.models.lm import LM
    lm = LM(cfg, remat="none")
    with pytest.raises(ValueError, match="MoE|capacity"):
        ContinuousBatcher(lm, None, slots=2, s_max=S_MAX)


def test_static_baseline_counts_useful_tokens(served):
    cfg, lm, params = served
    trace = _trace(cfg)
    reqs = [Request(rid=i, prompt_len=len(p), max_new=g, prompt=p,
                    temperature=t, t_submit=0.0)
            for i, (p, g, t) in enumerate(trace)]
    rep = run_static(lm, params, reqs, seed=0, s_max=S_MAX, slots=3)
    assert rep.generated == sum(g for _, g, _ in trace)
    assert 0.0 < rep.occupancy <= 1.0


@pytest.mark.slow
@pytest.mark.parametrize("arch", ["xlstm-125m", "musicgen-large",
                                  "llama-3.2-vision-11b"])
def test_streamed_tokens_match_offline_all_frontends(arch):
    """Same invariant across recurrent (xLSTM), audio-frame, and
    vision frontends — exercises frames/img_embeds routing through the
    bucketed group prefill and the gated decode."""
    cfg = get_config(arch, smoke=True)
    from repro.models.lm import LM
    lm = LM(cfg, remat="none")
    params, _ = lm.init(jax.random.PRNGKey(0))
    b = ContinuousBatcher(lm, params, slots=2, s_max=S_MAX, seed=3)
    rng = np.random.default_rng(1)
    for i in range(4):
        pl = int(rng.integers(3, 12))
        prompt = (None if cfg.frontend == "audio_frames"
                  else rng.integers(0, cfg.vocab, pl).astype(np.int32))
        b.submit(prompt, int(rng.integers(3, 8)), prompt_len=pl,
                 temperature=0.6 if i % 2 else 0.0)
    rep = b.run()
    for r in rep.requests:
        ref = decode_offline(lm, params, r, seed=3, s_max=S_MAX)
        assert r.out == ref, f"{arch} rid {r.rid}"


def test_serve_main_metrics(tmp_path):
    from repro.launch.serve import main
    m = main(["--arch", "smollm-135m", "--smoke", "--slots", "2",
              "--requests", "4", "--prompt-len-range", "3", "10",
              "--gen-range", "3", "6", "--static",
              "--plan-cache", str(tmp_path)])
    assert m["plan"]["source"] == "cold"
    assert m["continuous"]["tok_per_s"] > 0
    assert m["static"]["tok_per_s"] > 0
    assert m["continuous"]["requests"] == 4
    # second invocation: the persisted plan is a hit
    m2 = main(["--arch", "smollm-135m", "--smoke", "--slots", "2",
               "--requests", "4", "--prompt-len-range", "3", "10",
               "--gen-range", "3", "6",
               "--plan-cache", str(tmp_path)])
    assert m2["plan"]["source"] == "hit"
    assert m2["plan"]["fetch_ms"] < 50
