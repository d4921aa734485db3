"""Every file a cell names loads by name, every cell's driver runs end
to end at the program's smoke size on the CPU, and the real command
refuses to run off a TPU."""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

import harness
import model
import smoke

BENCH = harness.benchmark()
CELLS = [w["name"] for w in BENCH["workloads"]]
ROOT = harness.ROOT


def test_every_named_file_loads():
    kinds = set()
    for w in BENCH["workloads"]:
        spec = model.spec_of(model.load_config(w["config"]))
        harness.load_module("costs", spec.family)
        t = harness.traffic(w["traffic"])
        kinds.add(t["kind"])
        assert callable(harness.load_module("drivers", t["kind"]).run)
        assert all(isinstance(v, (int, float))
                   for v in harness.limits(w["name"]).values())
        assert "smoke" in t
        assert all(isinstance(v, (int, float))
                   for v in harness.limits(w["name"], smoke=True).values())
    for m in BENCH["per_layer"]:
        assert callable(harness.load_module("metrics", m["name"]).read)
    for c in BENCH["configs"]:
        assert (ROOT / c["file"]).exists()
    assert harness.peaks("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(KeyError):
        harness.peaks("some other chip")


def test_every_metric_a_cell_reports_moves_one_it_reports():
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    for m in BENCH["per_layer"]:
        for cell in m["workloads"]:
            assert cell in e2e[m["moves"]].get("workloads", [cell])
    for cell in CELLS:
        got = [m["name"] for m in harness.cell_metrics(BENCH, cell,
                                                        "end_to_end")]
        assert "setup_s" in got and len(got) >= 2
        assert harness.cell_metrics(BENCH, cell, "per_layer")


@pytest.mark.parametrize("cell", CELLS)
def test_driver_end_to_end_at_smoke_size(cell, tmp_path):
    ctx = smoke.context(cell, seed=2**33 + 7, out_dir=tmp_path)
    out = harness.load_module("drivers", ctx.traffic["kind"]).run(ctx)
    assert out.correct, out.checks
    assert out.compiles_in_window == 0
    assert out.attempted > 0 and out.failed == 0
    assert out.setup_s > 0
    names = {m["name"] for m in harness.cell_metrics(BENCH, cell,
                                                      "end_to_end")}
    assert names - {"setup_s"} <= set(out.e2e)
    assert all(v > 0 for v in out.e2e.values())


@pytest.mark.parametrize("cell", [c for c in CELLS if harness.traffic(
    harness.workload(BENCH, c)["traffic"])["kind"] != "train_steps"])
def test_traced_serving_counts_the_servers_work(cell, tmp_path,
                                                 monkeypatch):
    """The server's busy stretches land on the trace's clock where the
    host clock puts them (the CPU trace has no device plane to be busy)."""
    import dataclasses

    import trace
    seen = {}
    real = harness.Profile.summary

    def summary(self, work=None):
        seen.update(t_mark=self._t_mark, work=list(work))
        return real(self, work)

    monkeypatch.setattr(harness.Profile, "summary", summary)
    ctx = dataclasses.replace(smoke.context(cell, out_dir=tmp_path),
                              trace=True)
    out = harness.load_module("drivers", ctx.traffic["kind"]).run(ctx)
    tr = out.facts["trace"]
    host = [(seen["t_mark"], seen["t_mark"] + tr.window_s)]
    assert tr.work_s > 0
    assert tr.work_s == pytest.approx(trace.overlap_ns(seen["work"], host),
                                      abs=2e-3)
    assert tr.busy_work_s <= tr.busy_s


def test_a_cell_is_added_by_files_alone(tmp_path):
    """A copy of the benchmark gains a cell from a new traffic file, a new
    checks file and a ``workloads`` entry; its rehearsal runs it with no
    file of the harness edited."""
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    for p in BENCH["paths"]:
        shutil.copytree(ROOT / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    (tmp_path / "src").symlink_to(ROOT / "src")
    here = tmp_path / "benchmarks" / "chip"
    w = dict(harness.workload(BENCH, CELLS[0]))
    mix = json.loads((here / "traffic" / f"{w['traffic']}.json").read_text())
    mix["about"] = "a copy of an existing mix under a new name"
    (here / "traffic" / "added_mix.json").write_text(json.dumps(mix))
    name = f"{w['config']}.added_mix"
    shutil.copy(here / "checks" / f"{w['name']}.json",
                here / "checks" / f"{name}.json")
    bench = json.loads((tmp_path / "BENCHMARK.json").read_text())
    bench["workloads"].append(dict(w, name=name, traffic="added_mix"))
    for m in bench["end_to_end"] + bench["per_layer"]:
        if w["name"] in m.get("workloads", []):
            m["workloads"].append(name)
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    r = subprocess.run(
        [sys.executable, "-m", "pytest", "-q", "-p", "no:cacheprovider",
         "benchmarks/chip/tests/test_rehearsal.py", "-k",
         f"every_named or every_metric or {name}"],
        cwd=tmp_path, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stdout[-3000:]
    assert "4 passed" in r.stdout       # the new cell traced and untraced


def _run(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload", CELLS[0],
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=120)


def test_command_refuses_without_a_tpu():
    r = _run(ROOT)
    assert r.returncode != 0
    assert r.stdout.strip() == ""
    assert "no TPU" in r.stderr


def test_command_refuses_with_only_its_own_files(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    for p in BENCH["paths"]:
        shutil.copytree(ROOT / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    r = _run(tmp_path)
    assert r.returncode != 0
    assert r.stdout.strip() == ""


def test_result_line_holds_the_contracts_keys():
    out = harness.Outcome(e2e={"ttft_p90_s": 1.5, "tpot_p90_ms": 20.0},
                          facts={}, attempted=3, failed=0,
                          checks=[["served_token_gap", 0.1, 0.5]],
                          setup_s=9.0, compiles_in_window=0)
    dev = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
           "memory_peak_bytes": 5}
    line = harness.result_line(BENCH, CELLS[0], out, dev, False)
    assert list(line)[:5] == ["correct", "attempted", "failed", "metrics",
                              "device"]
    assert list(line)[-1] == "checks"
    assert line["metrics"]["setup_s"] == {"value": 9.0, "unit": "s"}
    json.dumps(line)
