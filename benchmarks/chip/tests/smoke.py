"""Smoke-size stand-ins for the CPU tests, found by name like the cells:
the widths of the program's own smoke config (``get_config(arch,
smoke=True)``) written as the configuration file states its sizes, each
traffic mix's ``smoke`` block, and each cell's smoke limits in
``checks/<cell>.json``.  Nothing here names a config, a mix or a cell."""
from __future__ import annotations

import copy
import dataclasses
import time
from pathlib import Path

import harness
import model

#: A size between the smoke configs and the cells': a vocabulary and
#: widths large enough for the control's rounding to flip served tokens.
WIDER = {"hidden_size": 256, "intermediate_size": 512,
         "num_attention_heads": 4, "head_dim": 64, "vocab_size": 32768,
         "num_hidden_layers": 4}
#: A published size and the program's ArchConfig field that holds it.
_ARCH_KEYS = {"hidden_size": "d_model", "intermediate_size": "d_ff",
              "num_attention_heads": "n_heads", "head_dim": "head_dim",
              "num_key_value_heads": "n_kv_heads", "vocab_size": "vocab",
              "num_hidden_layers": "n_layers"}


def _published(arch_cfg) -> dict:
    """The program's sizes under the configuration file's keys."""
    sizes = {k: getattr(arch_cfg, a) for k, a in _ARCH_KEYS.items()}
    sizes["head_dim"] = arch_cfg.resolved_head_dim
    return sizes


def context(cell: str, seed: int = 3, seconds: float = 2.0,
            out_dir: Path | None = None, wider: bool = False
            ) -> harness.Context:
    from repro.configs import get_config
    bench = harness.benchmark()
    w = harness.workload(bench, cell)
    conf = copy.deepcopy(model.load_config(w["config"]))
    arch_cfg = get_config(conf["arch"], smoke=True)
    if wider:
        arch_cfg = dataclasses.replace(
            arch_cfg, **{_ARCH_KEYS[k]: v for k, v in WIDER.items()})
    conf["published"].update(_published(arch_cfg))
    traffic = harness.traffic(w["traffic"])
    traffic.update(traffic["smoke"])
    spec = model.spec_of(conf)
    assert not model.program_mismatches(spec, arch_cfg)
    return harness.Context(
        cell=cell, conf=conf, spec=spec, arch_cfg=arch_cfg,
        traffic=traffic, seed=seed, seconds=seconds, trace=False,
        t_process=time.perf_counter(), out_dir=out_dir or Path("."),
        limits=harness.limits(cell, smoke=True), smoke=True,
        compiles=harness.CompileCounter())
