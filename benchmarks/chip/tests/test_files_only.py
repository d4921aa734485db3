"""What a later change may bring by new files and entries alone: a model
family the harness has not seen, a configuration cut to one chip's
share, a cell on a four-chip mesh.  Each is added to a copy of the
benchmark, with no file of the harness edited, and rehearsed there at
the program's smoke size on the CPU.  Also: the dense family's weights
and reference are those it had before it became a family file."""
import hashlib
import json
import os
import shutil
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import harness
import model
import smoke
import weights

BENCH = harness.benchmark()
ROOT = harness.ROOT
FOUR_CHIP = [w["name"] for w in BENCH["workloads"] if w["chips"] == 4]


def _copy(tmp_path):
    """A checkout of the benchmark alone, with the program beside it."""
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    for p in BENCH["paths"]:
        shutil.copytree(ROOT / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    (tmp_path / "src").symlink_to(ROOT / "src")
    return tmp_path / "benchmarks" / "chip"


def _add_cell(tmp_path, here, cell: dict, config: dict | None = None,
              reduced=()):
    """``cell`` (a ``workloads`` entry made from an existing one, whose
    metrics it reports too) and, where given, a ``configs`` entry."""
    bench = json.loads((tmp_path / "BENCHMARK.json").read_text())
    like = cell.pop("like")
    bench["workloads"].append(cell)
    for m in bench["end_to_end"] + bench["per_layer"]:
        if like in m.get("workloads", []):
            m["workloads"].append(cell["name"])
    if config is not None:
        bench["configs"].append({
            "name": config["name"], "source": config["source"],
            "file": f"benchmarks/chip/configs/{config['name']}.json",
            "reduced": list(reduced), "why": "a copy under a new name"})
        (here / "configs" / f"{config['name']}.json").write_text(
            json.dumps(config))
    shutil.copy(here / "checks" / f"{like}.json",
                here / "checks" / f"{cell['name']}.json")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))


def _rehearse(tmp_path, cell: str):
    r = subprocess.run(
        [sys.executable, "-m", "pytest", "-q", "-p", "no:cacheprovider",
         "benchmarks/chip/tests/test_rehearsal.py", "-k",
         f"every_named or every_metric or {cell}"],
        cwd=tmp_path, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, r.stdout[-3000:]
    assert "4 passed" in r.stdout       # the new cell traced and untraced


def _edited(tmp_path) -> list[str]:
    """Files of the copy that differ from the benchmark's own."""
    out = []
    for p in BENCH["paths"]:
        for f in (ROOT / p).rglob("*"):
            if f.is_file() and "__pycache__" not in f.parts:
                if (tmp_path / f.relative_to(ROOT)).read_bytes() \
                        != f.read_bytes():
                    out.append(str(f.relative_to(ROOT)))
    return out


def test_a_cell_is_added_by_files_alone(tmp_path):
    """A new traffic file, a new checks file and a ``workloads`` entry."""
    here = _copy(tmp_path)
    w = harness.workload(BENCH, BENCH["workloads"][0]["name"])
    mix = harness.traffic(w["traffic"])
    mix["about"] = "a copy of an existing mix under a new name"
    (here / "traffic" / "added_mix.json").write_text(json.dumps(mix))
    name = f"{w['config']}.added_mix"
    _add_cell(tmp_path, here, dict(w, name=name, traffic="added_mix",
                                   like=w["name"]))
    _rehearse(tmp_path, name)
    assert _edited(tmp_path) == []


def test_a_family_is_added_by_files_alone(tmp_path):
    """A family file and a costs file of a new name (here, copies of the
    dense ones), a configuration of that family, a mix, a checks file
    and the entries."""
    here = _copy(tmp_path)
    for kind in ("families", "costs"):
        shutil.copy(here / kind / "dense.py", here / kind / "dense_copy.py")
    conf = dict(model.load_config("smollm-360m"), name="smollm-360m-copy",
                family="dense_copy")
    mix = harness.traffic("chat")
    (here / "traffic" / "chat_copy.json").write_text(json.dumps(mix))
    _add_cell(tmp_path, here, {
        "name": "smollm-360m-copy.chat_copy", "config": "smollm-360m-copy",
        "traffic": "chat_copy", "chips": 1, "why": "a new family",
        "like": "smollm-360m.chat"}, conf)
    _rehearse(tmp_path, "smollm-360m-copy.chat_copy")
    assert _edited(tmp_path) == []


CUT = [{"key": "num_hidden_layers", "published": 32, "here": 16,
        "why": "half the depth: the other half would lie on a second chip"},
       {"key": "vocab_size", "published": 50304, "here": 6288,
        "why": "an eighth of the vocabulary, as eight chips would hold it"}]


def _cut_config() -> dict:
    conf = dict(model.load_config("stablelm-3b"), name="stablelm-3b-cut",
                reduced=CUT)
    conf.update({r["key"]: r["here"] for r in CUT})
    return conf


def test_a_cut_configuration_is_added_by_files_alone(tmp_path):
    here = _copy(tmp_path)
    _add_cell(tmp_path, here, {
        "name": "stablelm-3b-cut.reasoning", "config": "stablelm-3b-cut",
        "traffic": "reasoning", "chips": 1, "why": "a cut configuration",
        "like": "stablelm-3b.reasoning"}, _cut_config(),
        [r["key"] for r in CUT])
    _rehearse(tmp_path, "stablelm-3b-cut.reasoning")
    assert _edited(tmp_path) == []


MESH_CELL = {"name": "stablelm-3b.train_2k.2x2", "config": "stablelm-3b",
             "traffic": "train_2k.2x2", "chips": 4,
             "why": "a cell on a (data=2, model=2) mesh with ZeRO-3",
             "like": "smollm-360m.train_2k"}


def test_a_mesh_cell_is_added_by_files_alone(tmp_path):
    """The mix ``traffic/train_2k.2x2.json`` (a ``mesh`` and ``fsdp``),
    its checks file and the metric ``collective_share.train_2x2`` are
    files of the benchmark; the entries alone add the cell, which is then
    rehearsed on four CPU devices: end to end, its plan, its layered
    reference, its control and the faults a mesh can have.  Its checks
    file holds smoke limits only: until the chip's readings set its
    limits, the harness cannot judge the cell on the chip."""
    here = _copy(tmp_path)
    cell = dict(MESH_CELL)
    checks = here / "checks" / f"{cell['name']}.json"
    assert "limits" not in json.loads(checks.read_text())
    shutil.copy(checks, tmp_path / "checks.json")
    _add_cell(tmp_path, here, cell)
    shutil.copy(tmp_path / "checks.json", checks)
    bench = json.loads((tmp_path / "BENCHMARK.json").read_text())
    bench["per_layer"].append({
        "name": "collective_share.train_2x2", "unit": "%", "better": "lower",
        "source": "device_trace", "layer": "collectives",
        "moves": "train_tok_s", "workloads": [cell["name"]]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    tests = [f"benchmarks/chip/tests/{f}.py" for f in (
        "test_rehearsal", "test_files_only", "test_faults", "test_control")]
    r = subprocess.run(
        [sys.executable, "-m", "pytest", "-q", "-p", "no:cacheprovider",
         *tests, "-k", "2x2 or every_metric"],
        cwd=tmp_path, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=1500)
    assert r.returncode == 0, r.stdout[-3000:]
    assert "8 passed" in r.stdout, r.stdout[-1000:]
    assert _edited(tmp_path) == []


def test_the_cut_reaches_program_weights_reference_and_costs():
    from repro.configs import get_config
    from repro.models.lm import LM
    fam = harness.family("dense")
    spec, cfg = model.cut(_cut_config(), fam, get_config("stablelm-3b"))
    assert (spec.layers, spec.vocab) == (16, 6288)
    assert (cfg.n_layers, cfg.vocab) == (16, 6288)
    assert cfg.d_model == spec.d_model == 2560          # widths as published
    tree, _ = LM(cfg).init(None, abstract=True)
    assert weights.check_tree(fam.layout(spec), tree) == []
    assert fam.layout(spec)["embed"][0] == (6288, 2560)
    assert fam.layout(spec)["group0/b0/mix/w_q"][0][0] == 16
    have = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(tree))
    assert harness.load_module("costs", "dense").params(spec) == have
    ctx = smoke.context("stablelm-3b.reasoning")
    small = dict(_cut_config(), **{k: model.field_value(ctx.arch_cfg, f)
                                   for k, f in fam.FIELDS.items()
                                   if k not in ("num_hidden_layers",
                                                "vocab_size")})
    spec, cfg = model.cut(small, fam, ctx.arch_cfg)
    w = weights.make(fam.layout(spec), 5)
    tok = jnp.zeros((1, 8), jnp.int32)
    assert fam.logits(spec, w, tok).shape == (1, 8, 6288)


def _bad_files(here, case: str) -> str:
    """The cell ``smollm-360m.chat`` with its files at odds."""
    conf = model.load_config("smollm-360m")
    if case == "no_field":
        conf["reduced"] = [{"key": "max_position_embeddings",
                            "published": 2048, "here": 1024, "why": "x"}]
        conf["max_position_embeddings"] = 1024
    elif case == "entry_not_run":
        conf["reduced"] = [{"key": "num_hidden_layers", "published": 32,
                            "here": 16, "why": "x"}]
    elif case == "mesh_not_chips":
        mix = harness.traffic("chat")
        mix["mesh"] = {"data": 2, "model": 2}
        (here / "traffic" / "chat.json").write_text(json.dumps(mix))
    (here / "configs" / "smollm-360m.json").write_text(json.dumps(conf))
    return {"no_field": "no field", "entry_not_run": "the file runs",
            "mesh_not_chips": "is not the cell's"}[case]


@pytest.mark.parametrize("case", ["no_field", "entry_not_run",
                                  "mesh_not_chips"])
def test_files_at_odds_exit_3_before_any_work(case, tmp_path):
    here = _copy(tmp_path)
    said = _bad_files(here, case)
    r = subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload",
         "smollm-360m.chat", "--seed", "1", "--seconds", "1", "--trace",
         "0"], cwd=tmp_path, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=120)
    assert r.returncode == 3
    assert r.stdout.strip() == ""
    assert said in r.stderr and "no TPU" not in r.stderr


@pytest.mark.parametrize("cell", FOUR_CHIP)
def test_four_chip_plan_divides_every_leaf(cell):
    """The plan for the cell's mesh shards every weight leaf, splits the
    heads evenly, and the weights made in its shardings are those made
    whole; a step keeps them so."""
    drv = harness.load_module("drivers", "train_steps")
    ctx = smoke.context(cell)
    assert ctx.mesh.devices.size == 4 and ctx.fsdp
    b = drv.build(ctx)
    sh = weights.flatten(b.shardings)
    assert not any(s.is_fully_replicated for s in sh.values())
    job = drv.Job(ctx, b)
    whole = weights.flatten(weights.make(b.layout, ctx.seed))
    for k, v in weights.flatten(job.params).items():
        assert len(v.sharding.device_set) == 4
        assert np.array_equal(np.asarray(v), np.asarray(whole[k])), k
    job.step()
    assert all(len(v.sharding.device_set) == 4 and not
               v.sharding.is_fully_replicated
               for v in jax.tree.leaves(job.params))
    job.close()


#: sha256 (first 16 hex digits) of the dense weights at the program's
#: smoke size, and of the reference's logits and the fp8 control's over
#: token ids drawn from the seed, as the harness made them when the
#: weight layout and the reference lived in ``weights.py`` and
#: ``reference.py``.
BEFORE = {
    ("smollm-360m.chat", 3): ["0d9398b7301ecd56", "c48a945816ccee63",
                              "3c5c5b14ca7b3a35"],
    ("smollm-360m.chat", 2**33 + 7): ["02c0951514d36f1d",
                                      "21b5fc0f90f3b374",
                                      "02fdb25cf9074f36"],
    ("stablelm-3b.reasoning", 3): ["c53da2c48b4741d5", "55864de170b660c8",
                                   "cba013f3afdc1bb3"],
    ("stablelm-3b.reasoning", 2**33 + 7): ["31e983276f8405a6",
                                           "5e7f5e981a2dafdb",
                                           "ddef780995d085ac"],
}


def _digest(data: bytes) -> str:
    return hashlib.sha256(data).hexdigest()[:16]


@pytest.mark.parametrize("cell,seed", sorted(BEFORE))
def test_dense_weights_and_reference_as_before(cell, seed):
    spec = smoke.context(cell).spec
    fam = harness.family(spec.family)
    w = weights.make(fam.layout(spec), seed)
    h = hashlib.sha256()
    for k, v in sorted(weights.flatten(w).items()):
        h.update(k.encode())
        h.update(np.asarray(v).tobytes())
    toks = jnp.asarray(np.random.default_rng(seed % 2**32).integers(
        0, spec.vocab, (2, 16), dtype=np.int32))
    got = [h.hexdigest()[:16]] + [
        _digest(np.asarray(jax.jit(
            lambda w, t, q=q: fam.logits(spec, w, t, q))(w, toks)).tobytes())
        for q in (None, "fp8")]
    assert got == BEFORE[cell, seed]


@pytest.mark.parametrize("cell", ["smollm-360m.train_2k"] + FOUR_CHIP)
def test_layered_gradient_is_the_whole_models(cell):
    """The reference's gradient, taken layer by layer in blocks of rows,
    is the gradient of its whole-model loss."""
    ctx = smoke.context(cell)
    fam = harness.family(ctx.spec.family)
    w32 = jax.tree.map(lambda a: a.astype(jnp.float32),
                       weights.make(fam.layout(ctx.spec), 5))
    rng = np.random.default_rng(0)
    tok, lab = (rng.integers(0, ctx.spec.vocab, (4, 32), dtype=np.int32)
                for _ in range(2))
    want_l, want_g = jax.jit(jax.value_and_grad(
        lambda w: fam.loss(ctx.spec, w, tok, lab)))(w32)
    got_l, got_g = fam.loss_and_grad(ctx.spec, w32, tok, lab, rows=2)
    assert float(got_l) == pytest.approx(float(want_l), rel=1e-6)
    for a, b in zip(jax.tree.leaves(want_g), jax.tree.leaves(got_g)):
        assert float(jnp.max(jnp.abs(a - b))) <= 1e-5 * float(
            jnp.max(jnp.abs(a)))
