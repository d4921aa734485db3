"""Reduce a profiler trace (``.xplane.pb``) to device busy and idle time,
device time per compiled program, the costliest device operations and
the longest idle gaps with what the host was doing in each.

The reduction works on plain ``Event`` tuples, so it can be checked on
hand-made events; :func:`read` turns a trace file into them.
"""
from __future__ import annotations

import glob
import os
import re
from bisect import bisect_right
from collections import defaultdict
from dataclasses import dataclass, field
from typing import NamedTuple

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


class Event(NamedTuple):
    plane: str
    line: str
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


@dataclass
class Summary:
    window_s: float
    busy_s: float                      # mean over devices
    devices: int
    work_s: float = 0.0                # the window's stretches with work
    busy_work_s: float = 0.0           # busy in them, mean over devices
    programs: dict = field(default_factory=dict)   # name -> [seconds, runs]
    program_ops: dict = field(default_factory=dict)  # name -> {op: seconds}
    top_ops: list = field(default_factory=list)    # [[name, seconds]]
    idle_gaps: list = field(default_factory=list)  # [[host activity, s]]

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    @property
    def idle_work_share(self) -> float | None:
        """Idle share of the stretches in which the system had work."""
        return 1.0 - self.busy_work_s / self.work_s if self.work_s else None

    def program(self, prefix: str) -> tuple[float, int]:
        """Device seconds and runs of every program whose name starts
        with ``prefix``."""
        t = n = 0
        for name, (s, runs) in self.programs.items():
            if name.startswith(prefix):
                t, n = t + s, n + runs
        return t, n

    def ops_of(self, prefix: str) -> dict[str, float]:
        """Device seconds of each operation run inside a program whose
        name starts with ``prefix``."""
        out: dict[str, float] = defaultdict(float)
        for name, ops in self.program_ops.items():
            if name.startswith(prefix):
                for op, s in ops.items():
                    out[op] += s
        return dict(out)


def find_trace(log_dir: str) -> str:
    files = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(files) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {log_dir}, "
                           f"found {files}")
    return files[0]


def read(path: str) -> list[Event]:
    """Every event of the trace, device and host, on one clock."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    out = []
    for plane in pd.planes:
        for line in plane.lines:
            for ev in line.events:
                out.append(Event(plane.name, line.name, ev.name,
                                 float(ev.start_ns), float(ev.duration_ns)))
    return out


def merged(intervals) -> list[tuple[float, float]]:
    """The union of ``(start, end)`` intervals as disjoint, sorted ones."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def union_ns(intervals) -> float:
    """Length of the union of ``(start, end)`` intervals."""
    return sum(e - s for s, e in merged(intervals))


def overlap_ns(a, b) -> float:
    """Length of the intersection of two unions of intervals."""
    a, b = merged(a), merged(b)
    total, i, j = 0.0, 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        total += max(0.0, hi - lo)
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def gaps(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    """The stretches of ``[lo, hi]`` that no interval covers."""
    out, t = [], lo
    for s, e in sorted(intervals):
        if s > t:
            out.append((t, min(s, hi)))
        t = max(t, e)
        if t >= hi:
            break
    if t < hi:
        out.append((t, hi))
    return [(s, e) for s, e in out if e > s]


_SUFFIX = re.compile(r"\(\d+\)$")
#: Operations whose events span the operations they run (a scan's loop):
#: left out of the costliest operations, which would count them twice.
CONTAINERS = ("while", "conditional", "call")


def op_name(event: str) -> str:
    """``%fusion.12 = bf16[8,960]{...} fusion(...)`` -> ``fusion.12``."""
    return event.split(" = ", 1)[0].strip().lstrip("%")


def program_name(module_event: str) -> str:
    """``jit_decode_step(12)`` -> ``jit_decode_step``."""
    return _SUFFIX.sub("", module_event).strip()


def _host_activity(host: list[Event], s: float, e: float) -> str:
    """What the host was doing in the gap ``[s, e]``: the shortest host
    event that covers half of it or more, else the one covering most."""
    cover = [(min(ev.end_ns, e) - max(ev.start_ns, s), ev) for ev in host]
    cover = [(ov, ev) for ov, ev in cover if ov > 0]
    if not cover:
        return "no host event"
    half = [ev for ov, ev in cover if ov >= 0.5 * (e - s)]
    if half:
        return min(half, key=lambda ev: ev.dur_ns).name
    return max(cover, key=lambda c: c[0])[1].name


def program_runs(mod_ev: list[Event], op_ev: list[Event]
                 ) -> list[tuple[Event, list[Event]]]:
    """Each run of a program on one device: its module event and the
    operations that start inside it (a loop's or call's own event spans
    the operations it runs and is left out)."""
    mods = sorted(mod_ev, key=lambda ev: ev.start_ns)
    starts = [m.start_ns for m in mods]
    runs = defaultdict(list)
    for ev in op_ev:
        i = bisect_right(starts, ev.start_ns) - 1
        if i >= 0 and ev.start_ns < mods[i].end_ns and not op_name(
                ev.name).startswith(CONTAINERS):
            runs[i].append(ev)
    return [(mods[i], ops) for i, ops in sorted(runs.items())]


def summarize(events: list[Event], t0_ns: float, t1_ns: float,
              n_top: int = 10, work=None) -> Summary:
    """Reduce the events of ``[t0_ns, t1_ns]`` (the traced window).  The
    device is busy while it runs a program: inside a module's event or an
    operation's.  ``work``: the ``(start, end)`` stretches in which the
    system had work, on the trace's clock; by default the whole window."""
    dev = [ev for ev in events if ev.plane.startswith(DEVICE_PREFIX)]
    planes = sorted({ev.plane for ev in dev})
    work = merged((max(s, t0_ns), min(e, t1_ns))
                  for s, e in ([(t0_ns, t1_ns)] if work is None else work)
                  if min(e, t1_ns) > max(s, t0_ns))
    busy, busy_work, programs, ops = 0.0, 0.0, {}, defaultdict(float)
    program_ops: dict = defaultdict(lambda: defaultdict(float))
    all_gaps = []
    for plane in planes:
        mine = [ev for ev in dev if ev.plane == plane]
        op_ev = [ev for ev in mine if ev.line == OPS_LINE]
        mod_ev = [ev for ev in mine if ev.line == MODULES_LINE]
        spans = [(max(ev.start_ns, t0_ns), min(ev.end_ns, t1_ns))
                 for ev in op_ev + mod_ev]
        spans = [(s, e) for s, e in spans if e > s]
        busy += union_ns(spans)
        busy_work += overlap_ns(spans, work)
        all_gaps += gaps(spans, t0_ns, t1_ns)
        for ev in mod_ev:
            p = programs.setdefault(program_name(ev.name), [0.0, 0])
            p[0] += ev.dur_ns * 1e-9
            p[1] += 1
        for ev in op_ev:
            name = op_name(ev.name)
            if not name.startswith(CONTAINERS):
                ops[name] += ev.dur_ns * 1e-9
        for mod, run in program_runs(mod_ev, op_ev):
            for ev in run:
                program_ops[program_name(mod.name)][op_name(ev.name)] += \
                    ev.dur_ns * 1e-9
    n = max(len(planes), 1)
    host = [ev for ev in events if not ev.plane.startswith(DEVICE_PREFIX)]
    longest = sorted(all_gaps, key=lambda g: g[0] - g[1])[:n_top]
    return Summary(
        window_s=(t1_ns - t0_ns) * 1e-9, busy_s=busy * 1e-9 / n,
        devices=len(planes), work_s=union_ns(work) * 1e-9,
        busy_work_s=busy_work * 1e-9 / n,
        programs={k: [v[0] / n, v[1] // n] for k, v in programs.items()},
        program_ops={p: {k: v / n for k, v in o.items()}
                     for p, o in program_ops.items()},
        top_ops=[[k, v / n] for k, v in sorted(
            ops.items(), key=lambda kv: -kv[1])[:n_top]],
        idle_gaps=[[_host_activity(host, s, e), (e - s) * 1e-9]
                   for s, e in longest])
