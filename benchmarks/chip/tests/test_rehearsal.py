"""Every file a cell names loads by name, every cell's driver runs end
to end at the program's smoke size on the CPU, and the real command
refuses to run off a TPU."""
import json
import os
import re
import shutil
import subprocess
import sys

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
        conf = model.load_config(w["config"])
        fam = harness.family(conf["family"])
        assert fam.spec_of(conf).family == conf["family"]
        harness.load_module("costs", conf["family"])
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


#: Compiles, on one CPU device, the train driver's step for the one-chip
#: train cell and ``repro.launch.train.build``'s step for the same job,
#: and prints each optimised HLO text as JSON.  ``build`` plans for every
#: device it sees, so this runs in a process that sees one.
_STEPS = r"""
import argparse, json, sys
import jax
import harness, smoke
from repro.configs.base import ShapeSpec
from repro.launch import train
from repro.launch.steps import batch_specs
ctx = smoke.context(sys.argv[1])
t = ctx.traffic
b = harness.load_module("drivers", "train_steps").build(ctx)
params = b.lm.init(None, True)[0]
state = jax.eval_shape(b.opt.init, params)
batch = batch_specs(b.cfg, ShapeSpec("cli", t["seq"], t["batch"], "train"))[0]
def text(fn, mesh):
    with jax.set_mesh(mesh):
        return fn.lower(params, state, batch, 0).compile().as_text()
out = {"driver": text(b.step_fn, b.mesh)}
for remat in (t["remat"], "none"):
    args = argparse.Namespace(
        arch=ctx.conf["arch"], smoke=True, steps=t["schedule_steps"],
        batch=t["batch"], seq=t["seq"], lr=t["lr"], remat=remat, fsdp=False)
    _, _, mesh, _, _, _, step = train.build(args)
    out[remat] = text(step, mesh)
print(json.dumps(out))
"""


def _strip(line: str) -> str:
    """``line`` without its ``metadata``, ``sharding`` and
    ``frontend_attributes`` groups (braces nest inside them)."""
    for key in (", metadata={", ", sharding={", ", frontend_attributes={"):
        while (i := line.find(key)) >= 0:
            j, depth = i + len(key), 1
            while depth:
                depth += {"{": 1, "}": -1}.get(line[j], 0)
                j += 1
            line = line[:i] + line[j:]
    return line


def _instructions(hlo: str) -> list[str]:
    """An optimised HLO text's computations, without the module header,
    the source tables, metadata and sharding annotations, and with each
    name numbered in order of first use."""
    out, skip = [], False
    for line in hlo.splitlines():
        if line.startswith("FileNames"):
            skip = True
        elif line.startswith(("%", "ENTRY")):
            skip = False
        if not skip and not line.startswith("HloModule"):
            out.append(_strip(line))
    names: dict = {}

    def rename(m):
        return names.setdefault(m.group(0), f"%v{len(names)}")

    return [re.sub(r"%[\w.\-]+", rename, line) for line in out]


@pytest.mark.parametrize("cell", [
    w["name"] for w in BENCH["workloads"] if w["chips"] == 1
    and harness.traffic(w["traffic"])["kind"] == "train_steps"])
def test_train_step_is_the_programs(cell):
    """The train driver builds ``train.build``'s step itself (for the
    cell's cut config and mesh); on one chip it compiles to the program's
    step instruction for instruction, and a step built otherwise (no
    remat) does not."""
    tests = harness.HERE / "tests"
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=1",
               PYTHONPATH=os.pathsep.join(
                   [str(tests), str(harness.HERE), str(ROOT / "src")]))
    r = subprocess.run([sys.executable, "-c", _STEPS, cell], cwd=ROOT,
                       env=env, capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    texts = json.loads(r.stdout.strip().splitlines()[-1])
    remat = harness.traffic(harness.workload(BENCH, cell)["traffic"])["remat"]
    assert remat != "none"
    driver = _instructions(texts["driver"])
    assert driver == _instructions(texts[remat])
    assert driver != _instructions(texts["none"])
