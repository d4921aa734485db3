"""Continuous-batching request scheduler over ``LM.decode_step``.

The hypergraph runner split (ready set vs. running set) applied to
token serving: a fixed-width **decode batch** of ``slots`` rows steps
every iteration, while a **request queue** feeds free slots through
shape-bucketed prefill *side steps*.  A slot is freed the moment its
request finishes (EOS or ``max_new``) and the next queued request is
admitted into it — the decode batch never drains to wait for stragglers
the way a static batch does, which is where the tok/s win over
lock-step serving comes from on mixed-length traces.

Correctness rests on three model-layer properties (``models/``):

* **per-slot positions** — ``init_caches(vector_pos=True)`` makes every
  cache position a ``(B,)`` vector, so slot ``i`` can sit at position
  417 while slot ``j`` is at 12;
* **active gating** — ``batch["active"]`` makes an inactive slot's
  caches pass through bit-identical to never stepping, so empty slots
  neither advance nor pollute anything;
* **row independence** — with MoE excluded (expert capacity couples
  rows through whole-batch token counts), every slot's computation is
  independent of its neighbours, so the streamed tokens are identical
  to offline per-request decode (:func:`decode_offline`;
  ``tests/test_scheduler.py`` pins this).

Prefill runs per group of same-bucket requests, padded to a
power-of-two bucket (:func:`prefill_bucket`) so at most ``log2``
distinct lengths ever compile.  Where every block is self-attention
with a GQA cache (``LM.prefill_fills_caches``) it is one full-sequence
forward per request, ``LM.prefill``, whose K/V fill the cache; other
configs (recurrent mixers, MLA's latent cache, cross-attention) run a
``lax.scan`` of gated ``decode_step``s.  The filled rows are scattered
into the free slots.

RNG: every request owns an independent stream,
``fold_in(PRNGKey(seed), request_id)``, and every draw inside it is
keyed by position — no key is ever reused across steps or requests
(the serve-driver bug this PR fixes), and the whole trace is
reproducible from ``seed`` alone.
"""
from __future__ import annotations

import functools
import time
from collections import deque
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from ..models.attention import KVCache

#: Per-model jit memo: ``jax.jit(lm.decode_step)`` binds a *new*
#: function object every time, so naively jitting in each batcher (or
#: each ``decode_offline`` call) recompiles everything from scratch —
#: the warm-path numbers would be compile benchmarks.  Keyed by model
#: identity with a strong reference held (LM dataclasses are
#: unhashable, and the ref keeps a dead model's id from being reused
#: by a live one); models are few and long-lived per process.
_JIT_MEMO: dict[int, tuple[object, dict]] = {}

def _step_compiler_options() -> dict:
    """Keep a row's arithmetic the same at every batch width, so the
    streamed tokens equal :func:`decode_offline`'s.

    On the TPU, XLA's dot strength reduction rewrites the vector×matrix
    products of a batch-1 step into VPU multiply-reduces (the compiled
    smollm-360m decode step keeps 2 MXU convolutions of 8), whose sums
    run in another order than the MXU's: batch-1 and batch-8 logits then
    differ in the last bf16 bit, and a near-tied greedy argmax flips.
    With it off, the logits of a v5e are bit-equal across widths.
    (Disallowing excess precision as well breaks that equality again.)"""
    if jax.default_backend() == "tpu":
        return {"xla_tpu_enable_dot_strength_reduction": False}
    return {}


def _jit(fn):
    return jax.jit(fn, compiler_options=_step_compiler_options())


def _jit_cache(lm) -> dict:
    ent = _JIT_MEMO.get(id(lm))
    if ent is None or ent[0] is not lm:
        ent = _JIT_MEMO[id(lm)] = (lm, {})
    return ent[1]


def _jitted_step(lm):
    cache = _jit_cache(lm)
    fn = cache.get("step")
    if fn is None:
        fn = cache["step"] = _jit(lm.decode_step)
    return fn


def _fit(filled, caches):
    """``LM.prefill``'s K/V cut to the length of ``caches``: the rows past
    it are padding, since :meth:`ContinuousBatcher.submit` keeps every
    request inside the cache."""
    def cut(f, c):
        n = c.k.shape[-3]
        return KVCache(f.k[..., :n, :, :], f.v[..., :n, :, :], f.pos)

    return jax.tree.map(cut, filled, caches,
                        is_leaf=lambda x: isinstance(x, KVCache))

__all__ = ["Request", "ServeReport", "ContinuousBatcher", "decode_offline",
           "greedy_margins", "run_static", "prefill_bucket"]

#: Distinct fold tag for a request's (single) image draw, so it can
#: never collide with a per-position draw.
_IMG_TAG = 0x494D47


#: The smallest prefill bucket: the batcher and :func:`decode_offline`
#: pad a prompt to the same bucket.
PREFILL_MIN = 16


def prefill_bucket(length: int, minimum: int = PREFILL_MIN) -> int:
    """Smallest power-of-two ≥ ``length`` (floor ``minimum``) — the
    padded prefill length, bounding distinct compiles to log2."""
    b = max(minimum, 1)
    while b < length:
        b *= 2
    return b


@dataclass
class Request:
    """One generation request plus its lifecycle bookkeeping."""
    rid: int
    prompt_len: int
    max_new: int
    #: prompt token ids, shape (prompt_len,); ``None`` for audio-frame
    #: frontends (frames are drawn from the request's RNG stream).
    prompt: np.ndarray | None = None
    temperature: float = 0.0
    #: generated token ids, in order.
    out: list[int] = field(default_factory=list)
    t_submit: float = 0.0
    t_admit: float = 0.0
    t_first: float = 0.0
    t_done: float = 0.0
    finish: str = ""        # "eos" | "length"


@dataclass
class ServeReport:
    requests: list[Request] = field(default_factory=list)
    generated: int = 0
    steps: int = 0
    prefill_s: float = 0.0
    decode_s: float = 0.0
    wall_s: float = 0.0
    occupancy: float = 0.0      # mean active-slot fraction per decode step
    slots: int = 0
    #: admit groups prefilled by each path, ``"pass"`` or ``"scan"``.
    prefill_groups: dict[str, int] = field(default_factory=dict)

    @property
    def tok_per_s(self) -> float:
        return self.generated / self.wall_s if self.wall_s else 0.0

    @property
    def decode_tok_per_s(self) -> float:
        return self.generated / self.decode_s if self.decode_s else 0.0

    def to_dict(self) -> dict:
        return {"requests": len(self.requests),
                "generated": self.generated, "steps": self.steps,
                "tok_per_s": self.tok_per_s,
                "decode_tok_per_s": self.decode_tok_per_s,
                "prefill_s": self.prefill_s, "decode_s": self.decode_s,
                "wall_s": self.wall_s, "occupancy": self.occupancy,
                "slots": self.slots,
                "prefill_groups": dict(self.prefill_groups)}


def _request_key(seed: int, rid: int) -> jax.Array:
    return jax.random.fold_in(jax.random.PRNGKey(seed), rid)


def _frames_at(key: jax.Array, pos: int, d_model: int) -> jax.Array:
    """The audio frontend's frame at ``pos`` in a request's stream —
    one draw per (request, position), reproducible offline."""
    return jax.random.normal(jax.random.fold_in(key, pos),
                             (1, 1, d_model), jnp.bfloat16)


def _image_of(key: jax.Array, n_img: int, d_model: int) -> jax.Array:
    return jax.random.normal(jax.random.fold_in(key, _IMG_TAG),
                             (1, n_img, d_model), jnp.bfloat16)


def _sample(logits_row: np.ndarray, key: jax.Array, pos: int,
            temperature: float) -> int:
    """Sampling rule shared by the batcher and the offline reference:
    greedy at temperature 0, else categorical keyed by the *input*
    position that produced these logits."""
    if temperature > 0:
        tok = jax.random.categorical(
            jax.random.fold_in(key, pos),
            jnp.asarray(logits_row) / temperature)
        return int(tok)
    return int(np.argmax(np.asarray(logits_row), axis=-1))


class ContinuousBatcher:
    """Admit/evict scheduler around a jitted ``decode_step``.

    Args:
        lm: the model (``repro.models.lm.LM``).
        params: its parameters.
        slots: decode batch width (fixed for the jit).
        s_max: cache capacity per slot; a request needs
            ``prompt_len + max_new <= s_max``.
        seed: root of every RNG stream (see module docstring).
        eos_id: token id that finishes a request early (``None``
            disables EOS detection — length-only termination).
    """

    def __init__(self, lm, params, *, slots: int, s_max: int,
                 seed: int = 0, eos_id: int | None = None):
        cfg = lm.cfg
        if any(ffn == "moe" for _, ffn in cfg.layer_kinds()):
            raise ValueError(
                "continuous batching requires row-independent compute; "
                f"{cfg.name} has MoE layers whose expert capacity couples "
                "slots through whole-batch token counts (serve MoE "
                "configs with the static path)")
        self.lm, self.params = lm, params
        self.cfg = cfg
        self.slots, self.s_max, self.seed = slots, s_max, seed
        self.eos_id = eos_id
        #: ``"pass"``: one ``LM.prefill`` forward fills a request's
        #: caches (every block self-attention GQA); ``"scan"``: a scan of
        #: gated decode steps (recurrent mixers, MLA, cross-attention).
        self.prefill_path = "pass" if lm.prefill_fills_caches else "scan"

        self.caches = lm.init_caches(slots, s_max, vector_pos=True)
        self._step = _jitted_step(lm)

        self.queue: deque[Request] = deque()
        self._next_rid = 0
        self.pos = np.zeros(slots, np.int32)
        self.active = np.zeros(slots, bool)
        self.tokens = np.zeros((slots, 1), np.int32)
        self.slot_req: list[Request | None] = [None] * slots
        self._slot_key: list[jax.Array | None] = [None] * slots
        self._slot_img = (np.zeros(
            (slots, cfg.n_img_tokens, cfg.d_model), np.float32)
            if cfg.frontend == "vision" else None)

    # -- submission ------------------------------------------------------
    def submit(self, prompt: np.ndarray | None, max_new: int, *,
               prompt_len: int | None = None,
               temperature: float = 0.0) -> Request:
        if prompt is not None:
            prompt = np.asarray(prompt, np.int32).reshape(-1)
            prompt_len = len(prompt)
        assert prompt_len is not None and prompt_len >= 1
        if prompt_len + max_new > self.s_max:
            raise ValueError(f"request needs {prompt_len + max_new} "
                             f"positions, cache holds {self.s_max}")
        req = Request(rid=self._next_rid, prompt_len=prompt_len,
                      max_new=max_new, prompt=prompt,
                      temperature=temperature,
                      t_submit=time.perf_counter())
        self._next_rid += 1
        self.queue.append(req)
        return req

    # -- prefill side step -----------------------------------------------
    def _prefill_fn(self, bucket: int, k: int):
        """One jitted executable per (bucket, group-width) doing the
        whole admit-side device work in a *single* dispatch: prefill
        ``k`` same-bucket requests at once, scatter each filled row into
        its target slot of the batch cache, and gather each request's
        last-prompt-position logits.  Batch-1 python prefill + per-leaf
        install was ~15 ms of dispatch per admit — more than the decode
        steps it was feeding — and burst admits (server start, a wave
        finishing together) prefill ``k`` requests for the price of one.

        The program takes ``(params, xs, lengths, big, slot_vec, small,
        img)`` on either path, ``xs`` time-major ``(bucket, k, 1[, d])``.
        On the ``"pass"`` path it runs one ``LM.prefill`` forward per
        request, whose K/V land at ``[slot, 0:bucket]``, cut to the cache
        (``small`` and ``img`` are unused: no config that takes this path
        has an image frontend); on the ``"scan"`` path it scans the gated
        prompt steps over ``small``, a zero batch-``k`` cache
        (:meth:`_zero_cache`), and installs whole rows."""
        cache = _jit_cache(self.lm)
        fn = cache.get(("prefill", bucket, k))
        if fn is not None:
            return fn
        prefill = (self._pass_prefill if self.prefill_path == "pass"
                   else self._scan_prefill)(bucket, k)
        fn = cache[("prefill", bucket, k)] = _jit(prefill)
        return fn

    def _pass_prefill(self, bucket: int, k: int):
        cfg, lm = self.cfg, self.lm
        groups = lm._groups()

        def row(params, big, r):
            """One request's prefill at width 1 and its install."""
            batch = {key: r[key][None] for key in r if key != "slot"}
            logits, filled = lm.prefill(params, batch)
            filled = _fit(filled, big)
            out = {}
            with jax.named_scope("prefill_install"):
                for gi, (_pattern, repeats) in enumerate(groups):
                    ax = 1 if repeats > 1 else 0
                    g = f"group{gi}"

                    def ins(b, f, ax=ax):
                        start = [0] * b.ndim
                        start[ax] = r["slot"]
                        return jax.lax.dynamic_update_slice(
                            b, f.astype(b.dtype), start)

                    out[g] = jax.tree.map(ins, big[g], filled[g])
            return out, logits[0, 0]

        def prefill(params, xs, lengths, big, slot_vec, small, img):
            # Rows run one after another, each at width 1: a row's
            # arithmetic is then its width-1 program's whatever the group
            # width (the CPU's matmuls round a row differently at other
            # widths), so it streams what ``decode_offline`` does.
            rows = {"lengths": lengths, "slot": slot_vec}
            if cfg.frontend == "audio_frames":
                rows["frames"] = jnp.swapaxes(xs[:, :, 0], 0, 1)
            else:
                rows["tokens"] = xs[:, :, 0].T
            return jax.lax.scan(functools.partial(row, params), big, rows)

        return prefill

    def _scan_prefill(self, bucket: int, k: int):
        cfg, lm = self.cfg, self.lm
        groups = lm._groups()

        def prefill(params, xs, lengths, big, slot_vec, small, img):
            def body(caches, x):
                t, inp = x
                batch = {"pos": jnp.full((k,), t, jnp.int32),
                         "active": t < lengths}
                if cfg.frontend == "audio_frames":
                    batch["frames"] = inp
                else:
                    batch["tokens"] = inp
                if img is not None:
                    batch["img_embeds"] = img
                logits, caches = lm.decode_step(params, batch, caches)
                return caches, logits[:, -1]

            small, logits = jax.lax.scan(
                body, small, (jnp.arange(bucket), xs))
            # install: batch axis of every leaf is 0, except inside
            # stacked (scanned) layer groups where axis 0 is layers.
            out = {}
            with jax.named_scope("prefill_install"):
                for gi, (_pattern, repeats) in enumerate(groups):
                    ax = 1 if repeats > 1 else 0
                    g = f"group{gi}"

                    def ins(b, s, ax=ax):
                        if ax == 0:
                            return b.at[slot_vec].set(s)
                        return b.at[:, slot_vec].set(s)

                    out[g] = jax.tree.map(ins, big[g], small[g])
            # logits: (bucket, k, vocab) → each request's row at its
            # own last prompt position.
            last = jnp.take_along_axis(
                logits, (lengths - 1)[None, :, None], axis=0)[0]
            return out, last                               # (k, vocab)

        return prefill

    def _zero_cache(self, k: int):
        """Immutable zero batch-``k`` cache template, built once per
        width (jax arrays are functional — no admit can corrupt it)."""
        cache = _jit_cache(self.lm)
        z = cache.get(("zeros", k, self.s_max))
        if z is None:
            z = cache[("zeros", k, self.s_max)] = self.lm.init_caches(
                k, self.s_max, vector_pos=True)
        return z

    def _admit_group(self, pairs: list[tuple[int, Request]],
                     bucket: int) -> None:
        """Prefill + install one same-bucket group of requests into
        their slots (a single device dispatch), then sample each
        request's first token."""
        cfg = self.cfg
        k = len(pairs)
        now = time.perf_counter()
        keys = []
        lengths = np.zeros(k, np.int32)
        slot_vec = np.zeros(k, np.int32)
        for i, (slot, req) in enumerate(pairs):
            req.t_admit = now
            keys.append(_request_key(self.seed, req.rid))
            lengths[i] = req.prompt_len
            slot_vec[i] = slot
        if cfg.frontend == "audio_frames":
            cols = []
            for i, (_slot, req) in enumerate(pairs):
                pad = jnp.zeros((bucket - req.prompt_len, 1, cfg.d_model),
                                jnp.bfloat16)
                cols.append(jnp.concatenate(
                    [_frames_at(keys[i], t, cfg.d_model)
                     for t in range(req.prompt_len)] + [pad]))
            xs = jnp.stack(cols, axis=1)   # (bucket, k, 1, d_model)
        else:
            toks = np.zeros((bucket, k, 1), np.int32)
            for i, (_slot, req) in enumerate(pairs):
                toks[:req.prompt_len, i, 0] = req.prompt
            xs = jnp.asarray(toks)
        img = (jnp.concatenate(
            [_image_of(kk, cfg.n_img_tokens, cfg.d_model) for kk in keys])
            if cfg.frontend == "vision" else None)
        small = (self._zero_cache(k) if self.prefill_path == "scan"
                 else None)
        self.caches, last = self._prefill_fn(bucket, k)(
            self.params, xs, jnp.asarray(lengths), self.caches,
            jnp.asarray(slot_vec), small, img)
        with TraceAnnotation("serve.prefill.wait"):
            last_np = np.asarray(last)
        t_first = time.perf_counter()
        with TraceAnnotation("serve.sample"):
            for i, (slot, req) in enumerate(pairs):
                tok = _sample(last_np[i], keys[i], req.prompt_len - 1,
                              req.temperature)
                req.out.append(tok)
                req.t_first = t_first
                self.pos[slot] = req.prompt_len
                self.active[slot] = True
                self.tokens[slot, 0] = tok
                self.slot_req[slot] = req
                self._slot_key[slot] = keys[i]
                if self._slot_img is not None:
                    self._slot_img[slot] = np.asarray(img[i], np.float32)
                self._maybe_finish(slot, tok)

    def _evict(self, slot: int, finish: str) -> None:
        req = self.slot_req[slot]
        req.finish = finish
        req.t_done = time.perf_counter()
        self.active[slot] = False
        self.slot_req[slot] = None
        self._slot_key[slot] = None

    def _maybe_finish(self, slot: int, tok: int) -> bool:
        req = self.slot_req[slot]
        if self.eos_id is not None and tok == self.eos_id:
            self._evict(slot, "eos")
            return True
        if len(req.out) >= req.max_new:
            self._evict(slot, "length")
            return True
        return False

    # -- main loop -------------------------------------------------------
    def _decode_batch(self) -> dict:
        cfg = self.cfg
        batch = {"pos": jnp.asarray(self.pos),
                 "active": jnp.asarray(self.active)}
        if cfg.frontend == "audio_frames":
            rows = [(_frames_at(self._slot_key[i], int(self.pos[i]),
                                cfg.d_model)[0]
                     if self.active[i]
                     else jnp.zeros((1, cfg.d_model), jnp.bfloat16))
                    for i in range(self.slots)]
            batch["frames"] = jnp.stack(rows)
        else:
            batch["tokens"] = jnp.asarray(self.tokens)
        if cfg.frontend == "vision":
            batch["img_embeds"] = jnp.asarray(self._slot_img, jnp.bfloat16)
        return batch

    @functools.partial(jax.profiler.annotate_function, name="serve.run")
    def run(self, max_steps: int | None = None) -> ServeReport:
        """Drain the queue: admit → step → sample/evict until every
        submitted request has finished.  Returns the serving report;
        per-request tokens live on the :class:`Request` objects.

        Profiler spans (``serve.*``; free while no trace is running) name
        the host's part of each step on the device trace's clock."""
        rep = ServeReport(slots=self.slots)
        occ_sum = 0.0
        t_start = time.perf_counter()
        budget = max_steps if max_steps is not None else (
            sum(r.max_new for r in self.queue) + len(self.queue) + 64)
        while self.queue or self.active.any():
            # admit: fill the free slots from the queue, grouped by
            # prefill bucket so each group is one batched side step.
            if self.queue:
                t0 = time.perf_counter()
                groups: dict[int, list[tuple[int, Request]]] = {}
                for slot in range(self.slots):
                    if not self.queue:
                        break
                    if not self.active[slot]:
                        req = self.queue.popleft()
                        b = prefill_bucket(req.prompt_len)
                        groups.setdefault(b, []).append((slot, req))
                        rep.requests.append(req)
                for b, pairs in sorted(groups.items()):
                    path = self.prefill_path
                    rep.prefill_groups[path] = (
                        rep.prefill_groups.get(path, 0) + 1)
                    with TraceAnnotation("serve.admit", bucket=b,
                                         width=len(pairs), path=path):
                        self._admit_group(pairs, b)
                if groups:
                    rep.prefill_s += time.perf_counter() - t0
            if not self.active.any():
                continue    # every admitted request finished at token 0
            # one decode step over the whole batch
            t0 = time.perf_counter()
            with TraceAnnotation("serve.decode.dispatch"):
                batch = self._decode_batch()
                logits, self.caches = self._step(self.params, batch,
                                                 self.caches)
            with TraceAnnotation("serve.decode.wait"):
                logits_np = np.asarray(logits[:, -1])
            rep.decode_s += time.perf_counter() - t0
            rep.steps += 1
            occ_sum += float(self.active.sum()) / self.slots
            with TraceAnnotation("serve.sample"):
                for slot in range(self.slots):
                    if not self.active[slot]:
                        continue
                    req = self.slot_req[slot]
                    tok = _sample(logits_np[slot], self._slot_key[slot],
                                  int(self.pos[slot]), req.temperature)
                    req.out.append(tok)
                    self.pos[slot] += 1
                    self.tokens[slot, 0] = tok
                    self._maybe_finish(slot, tok)
            if rep.steps >= budget:
                for slot in range(self.slots):
                    if self.active[slot]:
                        self._evict(slot, "budget")
                break
        rep.wall_s = time.perf_counter() - t_start
        rep.generated = sum(len(r.out) for r in rep.requests)
        rep.occupancy = occ_sum / rep.steps if rep.steps else 0.0
        return rep


# -- references ----------------------------------------------------------

def _prefill_offline(lm, params, req: Request, key: jax.Array, caches):
    """Where the batcher prefills in one pass: the same ``LM.prefill`` at
    batch 1 over the prompt padded to its bucket, its K/V written into
    ``caches`` (scalar positions).  Returns ``(logits (vocab,), caches)``,
    or ``None`` on the scan path."""
    cfg = lm.cfg
    if not lm.prefill_fills_caches:
        return None
    L = req.prompt_len
    bucket = prefill_bucket(L)
    batch = {"lengths": jnp.asarray([L], jnp.int32)}
    if cfg.frontend == "audio_frames":
        batch["frames"] = jnp.concatenate(
            [_frames_at(key, t, cfg.d_model) for t in range(L)]
            + [jnp.zeros((1, bucket - L, cfg.d_model), jnp.bfloat16)],
            axis=1)
    else:
        toks = np.zeros((1, bucket), np.int32)
        toks[0, :L] = req.prompt
        batch["tokens"] = jnp.asarray(toks)
    memo = _jit_cache(lm)
    fn = memo.get("one_pass")
    if fn is None:
        fn = memo["one_pass"] = _jit(lm.prefill)
    logits, filled = fn(params, batch)

    def fill(c, f):
        n = f.k.shape[-3]
        return KVCache(c.k.at[..., :n, :, :].set(f.k),
                       c.v.at[..., :n, :, :].set(f.v),
                       jnp.full(c.pos.shape, L, c.pos.dtype))

    caches = jax.tree.map(fill, caches, _fit(filled, caches),
                          is_leaf=lambda x: isinstance(x, KVCache))
    return logits[0, -1], caches


def decode_offline(lm, params, req: Request, *, seed: int, s_max: int,
                   eos_id: int | None = None) -> list[int]:
    """Single-request lock-step decode — the scheduler's oracle.

    Deliberately a *different* code path from the batcher: scalar cache
    positions (``dynamic_update_slice`` writes instead of per-slot
    scatter), no gating, batch 1 throughout, and the prompt prefilled
    alone: by one ``LM.prefill`` over the prompt padded to its bucket
    where the batcher prefills in one pass, else one decode
    step per prompt token, unpadded.  Row independence says the streamed
    tokens must match exactly; ``tests/test_scheduler.py`` asserts it."""
    cfg = lm.cfg
    key = _request_key(seed, req.rid)
    caches = lm.init_caches(1, s_max)
    step = _jitted_step(lm)
    img = (_image_of(key, cfg.n_img_tokens, cfg.d_model)
           if cfg.frontend == "vision" else None)

    def batch_at(t: int, tok: int | None) -> dict:
        batch = {"pos": jnp.asarray(t, jnp.int32)}
        if cfg.frontend == "audio_frames":
            batch["frames"] = _frames_at(key, t, cfg.d_model)
        elif tok is None:
            batch["tokens"] = jnp.asarray(req.prompt[t],
                                          jnp.int32).reshape(1, 1)
        else:
            batch["tokens"] = jnp.asarray(tok, jnp.int32).reshape(1, 1)
        if img is not None:
            batch["img_embeds"] = img
        return batch

    first = _prefill_offline(lm, params, req, key, caches)
    if first is None:
        for t in range(req.prompt_len):
            logits, caches = step(params, batch_at(t, None), caches)
        first = logits[0, -1], caches
    last, caches = first
    out: list[int] = []
    tok = _sample(np.asarray(last), key, req.prompt_len - 1,
                  req.temperature)
    out.append(tok)
    t = req.prompt_len
    while len(out) < req.max_new and not (eos_id is not None
                                          and tok == eos_id):
        logits, caches = step(params, batch_at(t, tok), caches)
        tok = _sample(np.asarray(logits[0, -1]), key, t, req.temperature)
        out.append(tok)
        t += 1
    return out


def greedy_margins(lm, params, req: Request, tokens: list[int], *,
                   s_max: int) -> np.ndarray:
    """Teacher-forced replay of ``tokens`` (a greedy stream for ``req``)
    through :func:`decode_offline`'s path: for each emitted token, how far
    its logit sits below the row maximum, in ulps of the logits' dtype at
    that maximum (0 where it is the argmax).

    Where streamed and offline tokens part, this tells a near-tie flipped
    by a last-bit rounding difference (a few ulps) from a stream that
    left the model (hundreds).  Token-stream frontends only."""
    caches = lm.init_caches(1, s_max)
    step = _jitted_step(lm)
    margins = np.zeros(len(tokens), np.float32)

    def margin(j, row):
        eps = float(jnp.finfo(row.dtype).eps)
        row = np.asarray(row, np.float32)
        top = float(row.max())
        ulp = eps * 2.0 ** np.floor(np.log2(max(abs(top), 1e-30)))
        margins[j] = (top - row[tokens[j]]) / ulp

    feed = list(np.asarray(req.prompt).reshape(-1)) + list(tokens[:-1])
    first = _prefill_offline(lm, params, req, None, caches)
    t0 = 0
    if first is not None:
        last, caches = first
        margin(0, last)
        t0 = req.prompt_len
    for t in range(t0, len(feed)):
        batch = {"pos": jnp.asarray(t, jnp.int32),
                 "tokens": jnp.asarray(feed[t], jnp.int32).reshape(1, 1)}
        logits, caches = step(params, batch, caches)
        j = t - (req.prompt_len - 1)
        if j >= 0:
            margin(j, logits[0, -1])
    return margins


def run_static(lm, params, requests: list[Request], *, seed: int,
               s_max: int, slots: int | None = None,
               eos_id: int | None = None) -> ServeReport:
    """The pre-PR lock-step baseline at the same hardware batch width:
    requests are grouped into waves of ``slots`` rows in submission
    order, each wave's prompts padded to its longest, and every row of
    a wave decodes until the wave's largest ``max_new`` — finished and
    short-prompt rows keep burning full steps, and no new request can
    start until the whole wave drains.  The report counts only useful
    tokens (each request's own ``max_new``), which is exactly why this
    loses to continuous batching on mixed-length traces."""
    cfg = lm.cfg
    slots = slots or len(requests)
    rep = ServeReport(slots=slots)
    if not requests:
        return rep
    step = _jitted_step(lm)
    t_start = time.perf_counter()
    for w0 in range(0, len(requests), slots):
        wave = requests[w0:w0 + slots]
        B = len(wave)
        l_max = max(r.prompt_len for r in wave)
        g_max = max(r.max_new for r in wave)
        keys = [_request_key(seed, r.rid) for r in wave]
        prompts = np.zeros((B, l_max), np.int32)
        for i, r in enumerate(wave):
            if r.prompt is not None:
                prompts[i, :r.prompt_len] = r.prompt
        imgs = (jnp.concatenate(
            [_image_of(k, cfg.n_img_tokens, cfg.d_model) for k in keys])
            if cfg.frontend == "vision" else None)

        def batch_at(t: int, toks: np.ndarray | None) -> dict:
            batch = {"pos": jnp.asarray(t, jnp.int32)}
            if cfg.frontend == "audio_frames":
                batch["frames"] = jnp.concatenate(
                    [_frames_at(k, t, cfg.d_model) for k in keys])
            elif toks is None:
                batch["tokens"] = jnp.asarray(prompts[:, t:t + 1])
            else:
                batch["tokens"] = jnp.asarray(toks)
            if imgs is not None:
                batch["img_embeds"] = imgs
            return batch

        caches = lm.init_caches(B, s_max)
        t_wave = time.perf_counter()
        logits = None
        for t in range(l_max):
            logits, caches = step(params, batch_at(t, None), caches)
        rep.prefill_s += time.perf_counter() - t_wave
        t0 = time.perf_counter()
        logits_np = np.asarray(logits[:, -1])
        toks = np.zeros((B, 1), np.int32)
        done = [False] * B
        for i, r in enumerate(wave):
            tok = _sample(logits_np[i], keys[i], l_max - 1,
                          r.temperature)
            r.out = [tok]
            toks[i, 0] = tok
            done[i] = eos_id is not None and tok == eos_id
        for g in range(1, g_max):
            logits, caches = step(params, batch_at(l_max + g - 1, toks),
                                  caches)
            logits_np = np.asarray(logits[:, -1])
            rep.steps += 1
            for i, r in enumerate(wave):
                tok = _sample(logits_np[i], keys[i], l_max + g - 1,
                              r.temperature)
                if not done[i] and len(r.out) < r.max_new:
                    r.out.append(tok)
                    done[i] = eos_id is not None and tok == eos_id
                toks[i, 0] = tok
        rep.decode_s += time.perf_counter() - t0
        for r in wave:
            r.t_first = r.t_first or time.perf_counter()
            r.t_done = time.perf_counter()   # wave finishes together
            r.finish = "length"
            rep.requests.append(r)
        rep.occupancy += sum(r.max_new for r in wave)
    rep.wall_s = time.perf_counter() - t_start
    rep.generated = sum(len(r.out) for r in rep.requests)
    rep.occupancy = (rep.occupancy
                     / max(1, (rep.steps + 1) * slots))
    return rep
