"""What the program names on the device trace: its host spans
(``jax.profiler.TraceAnnotation``: ``serve.*``, ``data.next_batch``) with
their args, and its compiled programs' named scopes
(``repro.models.lm.SCOPES``).

A TPU operation's event carries its HLO instruction's text and no
``op_name``, so an operation is joined to its scope through its
program's optimised HLO (``compiled.as_text()``): the instruction of the
same name, whose ``metadata={op_name=...}`` holds the scope path.  The
scope is the innermost known name among the path's components, any
``jvp(``/``transpose(`` wrapper removed.

:func:`summarize` leaves every number :func:`trace.summarize` gives alone
and adds the gaps' labels, the device seconds per (program, scope) and
the host time per decode step.
"""
from __future__ import annotations

import re
from collections import defaultdict
from dataclasses import dataclass, field
from typing import NamedTuple

import trace

#: The host spans the program opens; a gap's label names the innermost.
SPANS = ("serve.run", "serve.admit", "serve.prefill.wait", "serve.sample",
         "serve.decode.dispatch", "serve.decode.wait", "data.next_batch")
UNSCOPED = "unscoped"


class Event(NamedTuple):
    """A :class:`trace.Event` with the args of a host event."""
    plane: str
    line: str
    name: str
    start_ns: float
    dur_ns: float
    args: tuple = ()                   # ((key, value), ...)

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns

    @property
    def label(self) -> str:
        """``serve.admit[bucket=256,width=1]``."""
        if not self.args:
            return self.name
        return self.name + "[" + ",".join(f"{k}={v}" for k, v in
                                          self.args) + "]"


def read(path: str) -> list[Event]:
    """Every event of the trace, as :func:`trace.read`, with the args of
    host events (a device operation's stats are left out)."""
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(path).planes:
        host = not plane.name.startswith(trace.DEVICE_PREFIX)
        for line in plane.lines:
            for ev in line.events:
                out.append(Event(plane.name, line.name, ev.name,
                                 float(ev.start_ns), float(ev.duration_ns),
                                 tuple(ev.stats) if host else ()))
    return out


#: An instruction's line: its text up to the opcode (``%name = type``, as
#: its operation event's name begins), its name, and the rest.
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?(%?([\w.\-]+) = .*?) [\w\-]+\((.*)$",
                    re.M)
_OP_NAME = re.compile(r"metadata=\{[^}]*?op_name=\"([^\"]*)\"")
_WRAP = re.compile(r"^(?:\w+\()+|\)+$")


def scope_of(op_name: str, scopes) -> str:
    """The innermost of ``scopes`` among the path's components:
    ``jit(f)/while/body/closed_call/checkpoint/attn/dot_general`` ->
    ``attn``, ``transpose(jvp(head))/dot_general`` -> ``head``."""
    for part in reversed(op_name.split("/")):
        if _WRAP.sub("", part) in scopes:
            return _WRAP.sub("", part)
    return UNSCOPED


def hlo_instructions(hlo_text: str) -> dict[str, tuple[str, str]]:
    """Instruction name -> (its text up to the opcode, its ``op_name``,
    "" where it has none), for every instruction of an optimised HLO
    module's text."""
    out = {}
    for head, name, rest in _INSTR.findall(hlo_text):
        op = _OP_NAME.search(rest)
        out[name] = (head, op.group(1) if op else "")
    return out


def hlo_scopes(hlo_text: str, scopes) -> dict[str, str]:
    """Instruction name -> scope, for the instructions with an
    ``op_name``."""
    return {name: scope_of(op, scopes)
            for name, (_, op) in hlo_instructions(hlo_text).items() if op}


@dataclass
class SpanSummary:
    #: [[span > host activity, seconds, start - window start in s]]
    idle_gaps: list = field(default_factory=list)
    scopes: dict = field(default_factory=dict)     # program -> {scope: s}
    #: program -> the costliest unscoped operations, [[name, op_name, s]]
    unscoped: dict = field(default_factory=dict)
    decode_steps: int = 0                          # dispatch spans
    host_step_s: float = 0.0     # their dispatch + the sampling after them

    def scope_s(self, program: str, scope: str) -> float | None:
        """Device seconds of ``scope``'s operations in ``program``'s runs
        (mean over devices); ``None`` if the program's HLO was not given
        or no operation of it ran."""
        got = self.scopes.get(program)
        return got.get(scope, 0.0) if got else None

    def covered(self, program: str) -> float | None:
        """Share of ``program``'s operation seconds inside a scope."""
        got = self.scopes.get(program)
        if not got:
            return None
        return 1.0 - got.get(UNSCOPED, 0.0) / sum(got.values())

    @property
    def host_step_ms(self) -> float | None:
        if not self.decode_steps:
            return None
        return 1e3 * self.host_step_s / self.decode_steps


def _inside(ev: Event, outer: list[Event]) -> bool:
    return any(o.start_ns <= ev.start_ns and ev.end_ns <= o.end_ns
               for o in outer)


def _innermost_span(spans: list[Event], s: float, e: float) -> str | None:
    """The shortest span that covers half of ``[s, e]`` or more."""
    cover = [ev for ev in spans
             if min(ev.end_ns, e) - max(ev.start_ns, s) >= 0.5 * (e - s)]
    return min(cover, key=lambda ev: ev.dur_ns).label if cover else None


def _longest_gaps(dev: list[Event], t0: float, t1: float, n_top: int):
    """The idle gaps :func:`trace.summarize` reports, with their ends."""
    found = []
    for plane in sorted({ev.plane for ev in dev}):
        spans = [(max(ev.start_ns, t0), min(ev.end_ns, t1)) for ev in dev
                 if ev.plane == plane
                 and ev.line in (trace.OPS_LINE, trace.MODULES_LINE)]
        found += trace.gaps([(s, e) for s, e in spans if e > s], t0, t1)
    return sorted(found, key=lambda g: g[0] - g[1])[:n_top]


def _op_seconds(dev: list[Event], hlo: dict) -> dict:
    """Program -> {(instruction, op_name): seconds} over the operations
    of each run of a program whose HLO texts are in ``hlo`` (a loop's or
    call's own event spans the operations it runs and is left out, as in
    the costliest operations).  Of several programs of one name, a run
    is joined to the one whose instructions' texts begin the most of its
    operations' events."""
    parsed = {p: [hlo_instructions(t) for t in texts]
              for p, texts in hlo.items()}
    out: dict = defaultdict(lambda: defaultdict(float))
    for plane in sorted({ev.plane for ev in dev}):
        mods = [ev for ev in dev if ev.plane == plane
                and ev.line == trace.MODULES_LINE
                and trace.program_name(ev.name) in parsed]
        op_ev = [ev for ev in dev
                 if ev.plane == plane and ev.line == trace.OPS_LINE]
        for mod, ops in trace.program_runs(mods, op_ev):
            prog = trace.program_name(mod.name)
            texts = {ev.name for ev in ops}
            instrs = max(parsed[prog], key=lambda c: sum(
                t.startswith(c.get(trace.op_name(t), ("\0",))[0])
                for t in texts))
            for ev in ops:
                op = trace.op_name(ev.name)
                out[prog][op, instrs.get(op, ("", ""))[1]] += ev.dur_ns * 1e-9
    return out


def summarize(events: list[Event], t0_ns: float, t1_ns: float,
              hlo: dict | None = None, scopes=(), n_top: int = 10,
              ) -> SpanSummary:
    """The spans and scopes of the traced window ``[t0_ns, t1_ns]``.
    ``hlo``: program name (``jit_decode_step``) -> the optimised HLO
    texts of the programs of that name; ``scopes``: the scope names to
    find in them."""
    dev = [ev for ev in events if ev.plane.startswith(trace.DEVICE_PREFIX)]
    host = [ev for ev in events
            if not ev.plane.startswith(trace.DEVICE_PREFIX)]
    spans = [ev for ev in host if ev.name in SPANS]
    others = [ev for ev in host if ev.name not in SPANS]
    gaps = []
    for s, e in _longest_gaps(dev, t0_ns, t1_ns, n_top):
        label = trace._host_activity(others, s, e)
        span = _innermost_span(spans, s, e)
        gaps.append([f"{span} > {label}" if span else label,
                     (e - s) * 1e-9, (s - t0_ns) * 1e-9])
    inside = [ev for ev in spans if t0_ns <= ev.start_ns < t1_ns]
    admits = [ev for ev in spans if ev.name == "serve.admit"]
    dispatch = [ev for ev in inside if ev.name == "serve.decode.dispatch"]
    sample = [ev for ev in inside if ev.name == "serve.sample"
              and not _inside(ev, admits)]
    n_dev = max(len({ev.plane for ev in dev}), 1)
    by_scope, unscoped = {}, {}
    for prog, ops in _op_seconds(dev, hlo or {}).items():
        got = by_scope[prog] = defaultdict(float)
        for (_, op_name), secs in ops.items():
            got[scope_of(op_name, scopes)] += secs / n_dev
        unscoped[prog] = sorted(
            ([name, op_name, secs / n_dev]
             for (name, op_name), secs in ops.items()
             if scope_of(op_name, scopes) == UNSCOPED),
            key=lambda r: -r[2])[:n_top]
    return SpanSummary(
        idle_gaps=gaps,
        scopes={p: dict(s) for p, s in by_scope.items()}, unscoped=unscoped,
        decode_steps=len(dispatch),
        host_step_s=sum(ev.dur_ns for ev in dispatch + sample) * 1e-9)


def scope_share(facts: dict, program: str, scope: str) -> float | None:
    """Per-layer reader: device time of ``scope``'s operations in
    ``program`` over the program's device time, in %; ``None`` where
    the program names no such scope or its HLO was not read."""
    sp, tr = facts.get("spans"), facts.get("trace")
    if sp is None or tr is None or scope not in sp.scopes.get(program, {}):
        return None
    secs, _ = tr.program(program)
    return 100.0 * sp.scope_s(program, scope) / secs if secs else None


def host_step_ms(facts: dict) -> float | None:
    """Per-layer reader: host milliseconds per decode step in the traced
    stretch that the device cannot overlap, the step's dispatch and the
    sampling after it (``serve.decode.dispatch`` + ``serve.sample``)."""
    sp = facts.get("spans")
    return None if sp is None else sp.host_step_ms
