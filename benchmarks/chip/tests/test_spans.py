"""The spans and scopes reader: on hand-made events and HLO text, on the
trace recorded on a TPU v5e, and on a traced smoke-size run of each cell
through the recorders of ``spans_run.py``."""
import dataclasses
from pathlib import Path

import pytest

import harness
import smoke
import spans
import spans_run
import trace

DATA = Path(__file__).resolve().parent / "data" / "tpu_probe.xplane.pb"
DEV = "/device:TPU:0"
HOST = "/host:CPU"
E = spans.Event
SCOPES = ("embed", "attn", "ffn", "cache_gate", "head", "optimizer")


def _device_events():
    return [E(DEV, "XLA Modules", "jit_step(11)", 100, 50),
            E(DEV, "XLA Modules", "jit_step(11)", 300, 60),
            E(DEV, "XLA Modules", "jit_other(2)", 200, 10),
            E(DEV, "XLA Ops", "%fusion.1 = bf16[8] fusion(x), kind=kLoop",
              100, 30),
            E(DEV, "XLA Ops", "%fusion.2 = bf16[8] fusion(y)", 120, 30),
            E(DEV, "XLA Ops", "%copy = bf16[8] copy(z)", 200, 10),
            E(DEV, "XLA Ops", "%while.3 = (s32[]) while(w)", 300, 60),
            E(DEV, "XLA Ops", "%fusion.1 = bf16[8] fusion(x), kind=kLoop",
              300, 40),
            E(DEV, "XLA Ops", "%select.9 = bf16[8] select(a, b, c)",
              340, 20),
            E(HOST, "main", "np.asarray_jax.Array_", 212, 85),
            E(HOST, "main", "whole run", 0, 10_000)]


def _span_events():
    return [E(HOST, "main", "serve.run", 5, 900),
            E(HOST, "main", "serve.admit", 205, 100,
              (("bucket", 256), ("width", 1))),
            E(HOST, "main", "serve.prefill.wait", 210, 90),
            E(HOST, "main", "serve.sample", 300, 4),
            E(HOST, "main", "serve.decode.dispatch", 150, 6),
            E(HOST, "main", "serve.decode.wait", 156, 44),
            E(HOST, "main", "serve.sample", 360, 10),
            E(HOST, "main", "serve.decode.dispatch", 371, 8)]


HLO = """HloModule jit_step, entry_computation_layout={(bf16[8]{0})->bf16[8]{0}}

%body (p: (s32[])) -> (s32[]) {
  %fusion.1 = bf16[8]{0} fusion(bf16[8]{0} %x), kind=kLoop, calls=%f1, metadata={op_name="jit(step)/while/body/closed_call/checkpoint/attn/dot_general" source_file="lm.py" source_line=3}
  ROOT %select.9 = bf16[8]{0} select(pred[8]{0} %a, bf16[8]{0} %b, bf16[8]{0} %c), metadata={op_name="jit(step)/cache_gate/jit(_where)/select_n"}
}

ENTRY %main (x: bf16[8]) -> bf16[8] {
  %fusion.2 = bf16[8]{0} fusion(bf16[8]{0} %y), kind=kLoop, calls=%f2, metadata={op_name="jit(step)/transpose(jvp(head))/dot_general"}
  %while.3 = (s32[]) while((s32[]) %w), condition=%c, body=%body, metadata={op_name="jit(step)/while"}
  ROOT %copy = bf16[8]{0} copy(bf16[8]{0} %z)
}
"""


def test_scope_is_the_innermost_known_name():
    assert spans.scope_of("jit(step)/while/body/closed_call/checkpoint/"
                          "attn/dot_general", SCOPES) == "attn"
    assert spans.scope_of("jit(step)/transpose(jvp(head))/dot_general",
                          SCOPES) == "head"
    assert spans.scope_of("jit(step)/jvp()/while/body/closed_call/ffn/"
                          "jit(silu)/mul", SCOPES) == "ffn"
    assert spans.scope_of("jit(step)/optimizer/attn_like/mul",
                          SCOPES) == "optimizer"
    assert spans.scope_of("jit(step)/while/body/dynamic_update_slice",
                          SCOPES) == spans.UNSCOPED
    assert spans.hlo_scopes(HLO, SCOPES) == {
        "fusion.1": "attn", "select.9": "cache_gate", "fusion.2": "head",
        "while.3": spans.UNSCOPED}


def test_gap_is_labelled_by_the_innermost_span_and_its_args():
    ev = _device_events() + _span_events()
    s = spans.summarize(ev, 50, 450)
    assert [[label, round(g * 1e9), round(at * 1e9)]
            for label, g, at in s.idle_gaps] == [
        # the host waits for a prefill inside an admit group
        ["serve.prefill.wait > np.asarray_jax.Array_", 90, 160],
        ["serve.run > whole run", 90, 310],
        ["serve.run > whole run", 50, 0],
        ["serve.decode.wait > whole run", 50, 100]]
    # without the wait span, the admit group with its args is innermost
    ev = [e for e in ev if e.name != "serve.prefill.wait"]
    s = spans.summarize(ev, 50, 450)
    assert ("serve.admit[bucket=256,width=1] > np.asarray_jax.Array_"
            in [g[0] for g in s.idle_gaps])


def test_summary_numbers_unchanged_by_span_events():
    """Every number of :func:`trace.summarize` reads the same once the
    program's spans are among the events, and the labelled gaps are the
    same gaps."""
    plain = _device_events()
    work = [(0, 130), (290, 320)]
    a = trace.summarize(plain, 50, 450, work=work)
    b = trace.summarize(plain + _span_events(), 50, 450, work=work)
    for f in ("window_s", "busy_s", "devices", "work_s", "busy_work_s",
              "programs", "top_ops"):
        assert getattr(a, f) == getattr(b, f), f
    assert a.idle_share == b.idle_share
    assert a.idle_work_share == b.idle_work_share
    assert [g for _, g in a.idle_gaps] == [g for _, g in b.idle_gaps]
    s = spans.summarize(plain + _span_events(), 50, 450)
    assert [g[1] for g in s.idle_gaps] == [g for _, g in a.idle_gaps]


def test_scope_seconds_from_hlo_text():
    ev = _device_events() + _span_events()
    s = spans.summarize(ev, 50, 450, hlo={"jit_step": [HLO]}, scopes=SCOPES)
    # jit_other has no HLO; the while loop's own event is left out
    assert set(s.scopes) == {"jit_step"}
    got = s.scopes["jit_step"]
    assert got == pytest.approx({"attn": (30 + 40) * 1e-9,
                                 "head": 30e-9, "cache_gate": 20e-9})
    assert s.scope_s("jit_step", "cache_gate") == pytest.approx(20e-9)
    assert s.covered("jit_step") == pytest.approx(1.0)
    assert s.scope_s("jit_other", "attn") is None
    # share of the program's device time (its module events: 110 ns)
    facts = {"spans": s, "trace": trace.summarize(ev, 50, 450)}
    assert spans.scope_share(facts, "jit_step", "cache_gate") == \
        pytest.approx(100 * 20 / 110)
    assert spans.scope_share(facts, "jit_step", "mamba") is None
    # a program without scopes (the parent's HLO) reads nothing
    bare = spans.summarize(ev, 50, 450, hlo={"jit_step": [HLO]},
                           scopes=())
    assert bare.covered("jit_step") == 0.0
    assert s.unscoped["jit_step"] == []
    assert [r[:2] for r in bare.unscoped["jit_step"]] == [
        ["fusion.1", "jit(step)/while/body/closed_call/checkpoint/attn/"
                     "dot_general"],
        ["fusion.2", "jit(step)/transpose(jvp(head))/dot_general"],
        ["select.9", "jit(step)/cache_gate/jit(_where)/select_n"]]
    assert bare.unscoped["jit_step"][0][2] == pytest.approx(70e-9)
    assert spans.scope_share(dict(facts, spans=bare), "jit_step",
                             "cache_gate") is None


def test_a_run_joins_the_program_of_its_name_whose_text_it_matches():
    """Programs of one name (the prefill of each bucket and width) are
    told apart by the instruction text each operation event begins with."""
    other = HLO.replace("bf16[8]{0} fusion(bf16[8]{0} %x)",
                        "bf16[16]{0} fusion(bf16[16]{0} %x)").replace(
        "checkpoint/attn/dot_general", "ffn/dot_general")
    ev = [E(DEV, "XLA Modules", "jit_step(1)", 0, 50),
          E(DEV, "XLA Modules", "jit_step(2)", 100, 50),
          E(DEV, "XLA Ops", "%fusion.1 = bf16[8]{0} fusion(bf16[8]{0} %x)",
            0, 40),
          E(DEV, "XLA Ops", "%fusion.1 = bf16[16]{0} fusion(bf16[16]{0} %x)",
            100, 30)]
    for texts in ([HLO, other], [other, HLO]):
        s = spans.summarize(ev, 0, 150, hlo={"jit_step": texts},
                            scopes=SCOPES)
        assert s.scopes["jit_step"] == pytest.approx({"attn": 40e-9,
                                                      "ffn": 30e-9})


def test_host_time_per_decode_step():
    s = spans.summarize(_device_events() + _span_events(), 50, 450)
    # two dispatches (6 + 8 ns) and the sampling after the decode step
    # (10 ns); the first token's sampling inside the admit is not a step's
    assert s.decode_steps == 2
    assert s.host_step_ms == pytest.approx((6 + 8 + 10) * 1e-6 / 2)
    assert spans.host_step_ms({"spans": s}) == s.host_step_ms
    assert spans.host_step_ms({}) is None
    assert spans.summarize(_device_events(), 50, 450).host_step_ms is None


def test_recorded_tpu_trace_reads_as_before():
    """The reader with args gives the events :func:`trace.read` gives,
    and the recorded operations join a hand-written HLO by name."""
    old = trace.read(str(DATA))
    new = spans.read(str(DATA))
    assert [tuple(e)[:5] for e in new] == [tuple(e) for e in old]
    mods = [e for e in new if e.line == trace.MODULES_LINE]
    t0, t1 = min(e.start_ns for e in mods), max(e.end_ns for e in mods)
    assert trace.summarize(new, t0, t1) == trace.summarize(old, t0, t1)
    ops = {trace.op_name(e.name) for e in new if e.line == trace.OPS_LINE}
    assert "convolution_tanh_fusion" in ops
    text = "HloModule jit_matmul_prog\n" + "\n".join(
        f'  %{op} = f32[] op(), metadata={{op_name="jit(m)/attn/x"}}'
        for op in sorted(ops))
    s = spans.summarize(new, t0, t1, hlo={"jit_matmul_prog": [text]},
                        scopes=SCOPES)
    busy = sum(e.dur_ns for e in new if e.line == trace.OPS_LINE
               and e.start_ns < t1 and any(
                   m.start_ns <= e.start_ns < m.end_ns for m in mods
                   if m.name.startswith("jit_matmul_prog")))
    assert s.scope_s("jit_matmul_prog", "attn") == pytest.approx(
        busy * 1e-9)


@pytest.mark.parametrize("cell", [w["name"] for w in
                                  harness.benchmark()["workloads"]])
def test_traced_smoke_run_reads_spans_and_hlo(cell, tmp_path, monkeypatch):
    """A traced smoke-size run through the recorders: the spans are on
    the trace where the harness maps the host clock, and the HLO of the
    programs the run called names their scopes."""
    from repro.models.lm import SCOPES as program_scopes
    for name in ("_SERVERS", "_STEPS", "_PROFILES"):
        monkeypatch.setattr(spans_run, name, [])
    spans_run.install(monkeypatch.setattr)
    ctx = dataclasses.replace(smoke.context(cell, out_dir=tmp_path),
                              trace=True)
    harness.load_module("drivers", ctx.traffic["kind"]).run(ctx)
    prof = spans_run._PROFILES[-1]
    assert spans_run.scope_names() == program_scopes

    def found(name):
        (text,) = prof.hlo[name]
        return set(spans.hlo_scopes(text, program_scopes).values())

    if ctx.traffic["kind"] == "train_steps":
        assert set(prof.hlo) == {"jit_train_step"}
        assert {"embed", "attn", "ffn", "layer_scan", "head", "loss",
                "optimizer"} <= found("jit_train_step")
    else:
        assert set(prof.hlo) == {"jit_decode_step", "jit_prefill"}
        assert {"embed", "attn", "ffn", "layer_scan", "cache_gate",
                "head"} <= found("jit_decode_step")
        assert all("prefill_install" in set(spans.hlo_scopes(
            t, program_scopes).values()) for t in prof.hlo["jit_prefill"])
        assert prof.spans.decode_steps > 0
        assert prof.spans.host_step_ms > 0
        # spans whose ends the host clock took land where the harness
        # maps it, within the host's own jitter
        assert prof.clock_ms["serve.prefill.wait"]
        assert all(abs(c) < 5.0 for v in prof.clock_ms.values() for c in v)
