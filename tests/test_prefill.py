"""One-pass prefill: ``LM.prefill`` fills the attention caches.

The serving prefill of a config whose every block is self-attention GQA
is one full-sequence forward whose K/V land in the batch cache.  Held
here to the decode-step scan it replaced (the ``"scan"`` path, which the
recurrent mixers, MLA and cross-attention still take): per-layer K/V
and last logits within bf16 rounding, a greedy stream that stays greedy
under a step-by-step replay, rows that do not depend on the group width,
and the path chosen from the config's mixer kinds.
"""
import dataclasses

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp                                          # noqa: E402

from repro.configs import get_config                             # noqa: E402
from repro.launch.scheduler import (ContinuousBatcher, Request,  # noqa: E402
                                    _request_key, _sample,
                                    decode_offline, prefill_bucket)
from repro.models.attention import KVCache                      # noqa: E402
from repro.models.lm import LM                                   # noqa: E402

S_MAX = 64
#: K/V and logits of the two paths agree to two bf16 ulps (8 significand
#: bits) at the largest magnitude of the scan's tensor.
BF16_ULPS = 2
#: A one-pass greedy token sits at most this many ulps below the argmax
#: of the step-by-step replay (the chip smoke check's near-tie bound).
TIE_ULPS = 4

#: GQA with RMSNorm; MHA with LayerNorm and 25 % rotary; a sliding
#: window of 16, which the prompts outgrow.
ATTN_ARCHS = ["smollm-135m", "stablelm-3b", "h2o-danube-3-4b"]


def _model(arch, **replace):
    cfg = get_config(arch, smoke=True)
    if replace:
        cfg = dataclasses.replace(cfg, **replace)
    lm = LM(cfg, remat="none")
    params, _ = lm.init(jax.random.PRNGKey(0))
    return cfg, lm, params


def _group(cfg, lengths, bucket, seed=0):
    """Time-major prompts ``(bucket, k, 1)`` as the batcher feeds them."""
    rng = np.random.default_rng(seed)
    xs = np.zeros((bucket, len(lengths), 1), np.int32)
    for i, n in enumerate(lengths):
        xs[:n, i, 0] = rng.integers(0, cfg.vocab, n)
    return jnp.asarray(xs), jnp.asarray(lengths, jnp.int32)


def _install(b, program, xs, lengths, slots):
    """Run a prefill program into the batcher's (empty) cache."""
    k = len(slots)
    return jax.jit(program)(b.params, xs, lengths, b.caches,
                            jnp.asarray(slots, jnp.int32),
                            b._zero_cache(k), None)


def _kv_rows(caches):
    return [c for c in jax.tree.leaves(
        caches, is_leaf=lambda x: isinstance(x, KVCache))
        if isinstance(c, KVCache)]


def _close(got, ref, what):
    got, ref = np.asarray(got, np.float32), np.asarray(ref, np.float32)
    scale = float(np.abs(ref).max())
    tol = BF16_ULPS * 2.0 ** (np.floor(np.log2(scale)) - 7)
    err = float(np.abs(got - ref).max())
    assert err <= tol, f"{what}: {err} > {tol} (scale {scale})"


def _step_margins(lm, params, req, tokens):
    """Teacher-forced replay of ``tokens`` through one decode step per
    position, prompt included: each token's ulps below the argmax."""
    caches = lm.init_caches(1, S_MAX)
    step = jax.jit(lm.decode_step)
    feed = list(req.prompt) + list(tokens[:-1])
    out = []
    for t, tok in enumerate(feed):
        logits, caches = step(params, {
            "pos": jnp.asarray(t, jnp.int32),
            "tokens": jnp.asarray(tok, jnp.int32).reshape(1, 1)}, caches)
        j = t - (req.prompt_len - 1)
        if j >= 0:
            row = np.asarray(logits[0, -1], np.float32)
            top = float(row.max())
            ulp = 2.0 ** (np.floor(np.log2(abs(top))) - 7)
            out.append((top - row[tokens[j]]) / ulp)
    return np.asarray(out)


@pytest.mark.parametrize("arch", ATTN_ARCHS)
def test_prefill_matches_decode_step_scan(arch):
    """Every layer's K/V at the prompt positions, each row's cache
    position and last logits: one pass == the scan of decode steps, to
    bf16 rounding, for a mixed-length group of width 3."""
    cfg, lm, params = _model(arch)
    b = ContinuousBatcher(lm, params, slots=3, s_max=S_MAX)
    bucket, lengths = 32, [9, 20, 32]
    xs, lens = _group(cfg, lengths, bucket)
    assert b.prefill_path == "pass"
    got, got_last = _install(b, b._pass_prefill(bucket, 3), xs, lens,
                             [0, 1, 2])
    ref, ref_last = _install(b, b._scan_prefill(bucket, 3), xs, lens,
                             [0, 1, 2])
    _close(got_last, ref_last, "last logits")
    for g, r in zip(_kv_rows(got), _kv_rows(ref)):
        np.testing.assert_array_equal(g.pos, r.pos)
        for i, n in enumerate(lengths):
            _close(g.k[..., i, :n, :, :], r.k[..., i, :n, :, :], f"k row {i}")
            _close(g.v[..., i, :n, :, :], r.v[..., i, :n, :, :], f"v row {i}")


@pytest.mark.parametrize("arch", ATTN_ARCHS)
def test_one_pass_greedy_stream_stays_greedy_under_decode_steps(arch):
    """A greedy stream prefilled in one pass is, token by token, the
    argmax of a replay by decode steps alone, up to a last-bit tie."""
    cfg, lm, params = _model(arch)
    rng = np.random.default_rng(4)
    for rid, n in enumerate([7, 20, 31]):
        prompt = rng.integers(0, cfg.vocab, n).astype(np.int32)
        req = Request(rid=rid, prompt_len=n, max_new=12, prompt=prompt)
        toks = decode_offline(lm, params, req, seed=0, s_max=S_MAX)
        m = _step_margins(lm, params, req, toks)
        assert m.shape == (12,) and m.max() <= TIE_ULPS, (rid, m)


@pytest.mark.parametrize("arch", ["smollm-135m", "stablelm-3b"])
def test_group_row_equals_width_one(arch):
    """A mixed-length group of width 3 in one bucket gives every row,
    bit for bit, what the row's own width-1 prefill gives: the installed
    K/V, the position and the last logits."""
    cfg, lm, params = _model(arch)
    b = ContinuousBatcher(lm, params, slots=3, s_max=S_MAX)
    bucket, lengths = 32, [17, 25, 32]
    xs, lens = _group(cfg, lengths, bucket, seed=2)
    slots = [2, 0, 1]
    group, last = _install(b, b._prefill_fn(bucket, 3), xs, lens, slots)
    for i, slot in enumerate(slots):
        alone, last1 = _install(b, b._prefill_fn(bucket, 1),
                                xs[:, i:i + 1], lens[i:i + 1], [slot])
        np.testing.assert_array_equal(np.asarray(last[i], np.float32),
                                      np.asarray(last1[0], np.float32))
        for g, a in zip(_kv_rows(group), _kv_rows(alone)):
            ax = g.pos.ndim - 1             # the slot axis (1: stacked)
            for leaf in ("k", "v", "pos"):
                np.testing.assert_array_equal(
                    np.take(np.asarray(getattr(g, leaf), np.float32), slot,
                            axis=ax),
                    np.take(np.asarray(getattr(a, leaf), np.float32), slot,
                            axis=ax))


def test_bucket_past_the_cache_fills_what_fits():
    """Where the bucket outgrows the cache, the one pass installs the
    K/V that fit, and they are the scan's, to bf16 rounding."""
    cfg, lm, params = _model("smollm-135m")
    b = ContinuousBatcher(lm, params, slots=2, s_max=24)
    bucket, lengths = 32, [9, 20]
    xs, lens = _group(cfg, lengths, bucket, seed=3)
    got, got_last = _install(b, b._pass_prefill(bucket, 2), xs, lens, [1, 0])
    ref, ref_last = _install(b, b._scan_prefill(bucket, 2), xs, lens, [1, 0])
    _close(got_last, ref_last, "last logits")
    for g, r in zip(_kv_rows(got), _kv_rows(ref)):
        assert g.k.shape == r.k.shape and g.k.shape[-3] == 24
        np.testing.assert_array_equal(g.pos, r.pos)
        for slot, n in zip([1, 0], lengths):
            _close(g.k[..., slot, :n, :, :], r.k[..., slot, :n, :, :], "k")
            _close(g.v[..., slot, :n, :, :], r.v[..., slot, :n, :, :], "v")


def test_prefill_without_lengths_is_full_length():
    """Without ``lengths`` every row is full length: the logits are the
    full-sequence forward's at the last position, every cache position
    is the sequence length, and the cache has ``init_caches``' layout."""
    cfg, lm, params = _model("smollm-135m")
    toks = jnp.asarray(np.random.default_rng(6).integers(
        0, cfg.vocab, (2, 24)), jnp.int32)
    logits, caches = jax.jit(lm.prefill)(params, {"tokens": toks})
    assert logits.shape == (2, 1, cfg.vocab)
    full = jax.jit(lm.logits_fn)(params, {"tokens": toks})
    _close(logits[:, 0], full[:, -1], "last logits")
    want = lm.init_caches(2, 24, abstract=True, vector_pos=True)
    assert (jax.tree.structure(caches) == jax.tree.structure(want))
    assert all(a.shape == w.shape and a.dtype == w.dtype for a, w in zip(
        jax.tree.leaves(caches), jax.tree.leaves(want)))
    assert all(bool((c.pos == 24).all()) for c in _kv_rows(caches))


def test_prefill_fills_no_cache_it_cannot():
    """A recurrent config's ``LM.prefill`` returns its logits alone."""
    cfg, lm, params = _model("xlstm-125m")
    assert not lm.prefill_fills_caches
    logits, caches = jax.jit(lm.prefill)(params, {
        "tokens": jnp.zeros((1, 16), jnp.int32)})
    assert logits.shape == (1, 1, cfg.vocab) and caches is None


MLA = dict(moe=None, family="dense")       # deepseek-v2's MLA, dense FFN

#: prompts of 18, 5 and 20 through 2 slots: admit groups of bucket 32
#: and 16, then 32.  A cache of 24 is shorter than bucket 32.
PATH_CASES = [("smollm-135m", {}, S_MAX, {"pass": 3}),
              ("stablelm-3b", {}, S_MAX, {"pass": 3}),
              ("h2o-danube-3-4b", {}, S_MAX, {"pass": 3}),
              ("musicgen-large", {}, S_MAX, {"pass": 3}),
              ("xlstm-125m", {}, S_MAX, {"scan": 3}),
              ("deepseek-v2-236b", MLA, S_MAX, {"scan": 3}),
              ("llama-3.2-vision-11b", {}, S_MAX, {"scan": 3}),
              ("smollm-135m", {}, 24, {"pass": 3})]


@pytest.mark.parametrize("arch,replace,s_max,groups", PATH_CASES,
                         ids=[f"{a}-{s}" for a, _, s, _ in PATH_CASES])
def test_prefill_path_follows_the_mixers(arch, replace, s_max, groups):
    """Self-attention GQA configs prefill in one pass, also into a cache
    shorter than the bucket; recurrent mixers, MLA and cross-attention
    take the scan.  ``ServeReport.prefill_groups`` counts
    each admit group by path, and the streams match ``decode_offline``."""
    cfg, lm, params = _model(arch, **replace)
    b = ContinuousBatcher(lm, params, slots=2, s_max=s_max, seed=1)
    rng = np.random.default_rng(7)
    for n in (18, 5, 20):
        prompt = (None if cfg.frontend == "audio_frames"
                  else rng.integers(0, cfg.vocab, n).astype(np.int32))
        b.submit(prompt, 3, prompt_len=n)
    rep = b.run()
    assert rep.prefill_groups == groups
    assert rep.to_dict()["prefill_groups"] == groups
    for r in rep.requests:
        assert r.out == decode_offline(lm, params, r, seed=1, s_max=s_max)


def test_oracle_prefills_the_request_alone():
    """``decode_offline`` prefills at batch 1 over the prompt padded to
    its bucket: its first token is the argmax of that ``LM.prefill``."""
    cfg, lm, params = _model("smollm-135m")
    n = 13
    prompt = np.random.default_rng(8).integers(0, cfg.vocab, n)
    req = Request(rid=0, prompt_len=n, max_new=1,
                  prompt=prompt.astype(np.int32))
    row = np.zeros((1, prefill_bucket(n)), np.int32)
    row[0, :n] = prompt
    logits, _ = jax.jit(lm.prefill)(params, {
        "tokens": jnp.asarray(row), "lengths": jnp.asarray([n], jnp.int32)})
    want = _sample(np.asarray(logits[0, -1]), _request_key(0, 0), n - 1, 0.0)
    assert decode_offline(lm, params, req, seed=0, s_max=S_MAX) == [want]
