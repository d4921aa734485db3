"""Smoke-size stand-ins for the CPU tests, found by name like the cells:
the widths of the program's own smoke config (``get_config(arch,
smoke=True)``) written under the keys the family reads, each cut key at
the value its configuration file runs, each traffic mix's ``smoke``
block, and each cell's smoke limits in ``checks/<cell>.json``.  Nothing
here names a config, a family, a mix or a cell."""
from __future__ import annotations

import copy
import time
from pathlib import Path

import harness
import model

#: A size between the smoke configs and the cells': a vocabulary and
#: widths large enough for the control's rounding to flip served tokens.
WIDER = {"hidden_size": 256, "intermediate_size": 512,
         "num_attention_heads": 4, "head_dim": 64, "vocab_size": 32768,
         "num_hidden_layers": 4}


def context(cell: str, seed: int = 3, seconds: float = 2.0,
            out_dir: Path | None = None, wider: bool = False
            ) -> harness.Context:
    from repro.configs import get_config
    bench = harness.benchmark()
    w = harness.workload(bench, cell)
    conf = copy.deepcopy(model.load_config(w["config"]))
    fam = harness.family(conf["family"])
    arch_cfg = get_config(conf["arch"], smoke=True)
    for k, v in (WIDER.items() if wider else ()):
        arch_cfg = model.replace_field(arch_cfg, fam.FIELDS[k], v)
    cut = {r["key"] for r in conf["reduced"]}
    conf.update({k: model.field_value(arch_cfg, f)
                 for k, f in fam.FIELDS.items() if k not in cut})
    spec, arch_cfg = model.cut(conf, fam, arch_cfg)
    traffic = harness.traffic(w["traffic"])
    traffic.update(traffic["smoke"])
    mesh, mesh_spec = harness.cell_mesh(
        harness.mesh_shape(traffic, w["chips"]))
    return harness.Context(
        cell=cell, conf=conf, spec=spec, arch_cfg=arch_cfg,
        traffic=traffic, seed=seed, seconds=seconds, trace=False,
        t_process=time.perf_counter(), out_dir=out_dir or Path("."),
        limits=harness.limits(cell, smoke=True), smoke=True,
        compiles=harness.CompileCounter(), chips=w["chips"], mesh=mesh,
        mesh_spec=mesh_spec, fsdp=bool(traffic.get("fsdp", False)))
