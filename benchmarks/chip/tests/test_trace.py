"""The trace reduction, on hand-made events and on a small trace
recorded on a TPU v5e (three runs of a 1024x1024 bf16 matmul program,
two of an elementwise one)."""
from pathlib import Path

import pytest

import trace

DATA = Path(__file__).resolve().parent / "data" / "tpu_probe.xplane.pb"
DEV = "/device:TPU:0"


def test_is_the_benchmarks_module():
    assert Path(trace.__file__).resolve().parent == DATA.parents[2]


def test_union_and_gaps_by_hand():
    spans = [(0, 10), (5, 12), (20, 25), (24, 24), (30, 31)]
    assert trace.union_ns(spans) == 12 + 5 + 1
    assert trace.gaps(spans, -5, 40) == [(-5, 0), (12, 20), (25, 30),
                                         (31, 40)]


def test_summary_by_hand():
    E = trace.Event
    ev = [E(DEV, "XLA Modules", "jit_step(11)", 100, 50),
          E(DEV, "XLA Modules", "jit_step(11)", 300, 60),
          E(DEV, "XLA Modules", "jit_other(2)", 200, 10),
          E(DEV, "XLA Ops", "fusion.1", 100, 30),
          E(DEV, "XLA Ops", "fusion.2", 120, 30),      # overlaps fusion.1
          E(DEV, "XLA Ops", "copy", 200, 10),
          E(DEV, "XLA Ops", "fusion.1", 300, 60),
          E("/host:CPU", "main", "sampling", 215, 80),
          E("/host:CPU", "main", "whole run", 0, 10_000)]
    s = trace.summarize(ev, 50, 450)
    assert s.window_s == pytest.approx(400e-9)
    # busy: [100,150] + [200,210] + [300,360] = 120 ns
    assert s.busy_s == pytest.approx(120e-9)
    assert s.idle_share == pytest.approx(1 - 120 / 400)
    assert s.program("jit_step") == (pytest.approx(110e-9), 2)
    assert s.program("jit_other") == (pytest.approx(10e-9), 1)
    assert s.top_ops[0] == ["fusion.1", pytest.approx(90e-9)]
    # idle: [50,100] 50, [150,200] 50, [210,300] 90, [360,450] 90
    assert [round(g * 1e9) for _, g in s.idle_gaps] == [90, 90, 50, 50]
    assert s.idle_gaps[0][0] == "sampling"          # covers [215, 295]
    assert s.idle_work_share == pytest.approx(s.idle_share)
    # work only in [0, 130] and [290, 320]: busy [100,130] + [300,320]
    w = trace.summarize(ev, 50, 450, work=[(290, 320), (0, 130)])
    assert w.work_s == pytest.approx((80 + 30) * 1e-9)
    assert w.busy_work_s == pytest.approx((30 + 20) * 1e-9)
    assert w.idle_work_share == pytest.approx(1 - 50 / 110)
    assert w.busy_s == s.busy_s


def test_a_program_without_its_operations_is_busy():
    """A module's event with no operation events inside it counts as
    busy: the device runs that program."""
    E = trace.Event
    ev = [E(DEV, "XLA Modules", "jit_scan(3)", 0, 100),
          E(DEV, "XLA Modules", "jit_step(1)", 150, 20),
          E(DEV, "XLA Ops", "fusion.1", 150, 20)]
    s = trace.summarize(ev, 0, 200)
    assert s.busy_s == pytest.approx(120e-9)
    assert s.program("jit_scan") == (pytest.approx(100e-9), 1)


def test_overlap_by_hand():
    a = [(0, 10), (5, 12), (20, 25)]
    b = [(8, 22), (24, 30)]
    assert trace.merged(a) == [(0, 12), (20, 25)]
    assert trace.overlap_ns(a, b) == 4 + 2 + 1
    assert trace.overlap_ns(a, []) == 0


def test_recorded_tpu_trace():
    events = trace.read(str(DATA))
    mods = [e for e in events if e.line == trace.MODULES_LINE]
    t0 = min(e.start_ns for e in mods)
    t1 = max(e.end_ns for e in mods)
    s = trace.summarize(events, t0, t1)
    assert s.devices == 1
    # device durations of each program's runs, read off the trace by hand
    assert s.program("jit_matmul_prog") == (
        pytest.approx((18905 + 16001 + 15752) * 1e-9), 3)
    assert s.program("jit_ew_prog") == (
        pytest.approx((28086 + 28003) * 1e-9), 2)
    # busy: every run of each program, from its module's start to its end
    busy = 18905 + 16001 + 15752 + 28086 + 28003
    assert s.busy_s == pytest.approx(busy * 1e-9)
    assert s.idle_share == pytest.approx(1 - busy / (t1 - t0))
