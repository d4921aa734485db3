"""Paper Fig. 11 analogue: IA+CA vs IA-only vs CA-only vs naive
parallelization.

Two measurement layers:

1. *Estimator layer* (always): the roofline QoR per arm.  Caveat — the
   naive arm *looks* competitive here, exactly as the paper observes that
   naive factor selection looks fine until the compiler has to implement
   it ("the compiler generates overly-complicated control logics …
   ultimately falling back to flawed designs").
2. *Compiled layer* (when dry-run artifacts exist, or ``--compile`` is
   passed): the real XLA SPMD compile per arm — temp bytes/device and
   collective bytes from the post-SPMD HLO.  This is where the CA-off
   arms collapse: GSPMD "involuntary full rematerialization" inflates
   temp memory by orders of magnitude (measured 2.3 TiB/device on the
   incoherent deepseek-v3 plan vs ~106 GiB coherent).
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
from pathlib import Path

from repro.configs import SHAPES, get_config
from repro.core import SINGLE_POD, build_lm_graph, optimize

ARMS = (("hida", True, True), ("ia", True, False),
        ("ca", False, True), ("naive", False, False))
ARTIFACT_DIR = Path(__file__).resolve().parents[1] / "experiments" / "dryrun"


def _artifact(arch, shape, strategy):
    suffix = "" if strategy == "hida" else f"__{strategy}"
    p = ARTIFACT_DIR / f"{arch}__{shape}__16x16{suffix}.json"
    if p.exists():
        return json.loads(p.read_text())
    return None


def _compile_arm(arch, shape, strategy):
    # The dry-run compiles for a 256-device mesh of forced host devices:
    # the child stays on the CPU and never reaches for an accelerator
    # this process may hold.
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["PYTHONPATH"] = str(Path(__file__).resolve().parents[1] / "src")
    subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun", "--arch", arch,
         "--shape", shape, "--strategy", strategy],
        env=env, capture_output=True, text=True, timeout=1800)
    return _artifact(arch, shape, strategy)


def run(report, arch: str = "smollm-360m", factors=(4, 16, 64, 256),
        compile_arms: bool = False) -> None:
    cfg = get_config(arch)
    shape = SHAPES["train_4k"]

    # -- estimator sweep over max parallel factor --------------------------------
    for pf in factors:
        row = {}
        for name, ia, ca in ARMS:
            g = build_lm_graph(cfg, shape)
            _, _, rep = optimize(g, SINGLE_POD, ia=ia, ca=ca,
                                 training=True, max_parallel_factor=pf)
            row[name] = rep
        derived = "|".join(
            f"{name}:t={r.cost.total_s*1e3:.2f}ms,"
            f"hbm={r.cost.hbm_bytes_per_device/2**30:.2f}GiB"
            for name, r in row.items())
        report.add(f"ablation_iaca_est/{arch}/pf{pf}",
                   us_per_call=row["hida"].cost.total_s * 1e6,
                   derived=derived)

    # -- compiled reality per arm --------------------------------------------------
    for name, _, _ in ARMS:
        art = _artifact(arch, "train_4k", name)
        if art is None and compile_arms:
            art = _compile_arm(arch, "train_4k", name)
        if art is None or art.get("status") != "ok":
            continue
        mem = art["memory_analysis"]
        temp = mem["temp_size_in_bytes"]
        coll = art["collectives"].get("scaled_total_bytes",
                                      art["collectives"]["total_bytes"])
        report.add(
            f"ablation_iaca_compiled/{arch}/{name}",
            us_per_call=art.get("compile_s", 0.0) * 1e6,
            derived=f"temp_GiB_per_dev={temp/2**30:.2f}|"
                    f"collective_GiB={coll/2**30:.2f}|"
                    f"compile_s={art.get('compile_s', 0):.0f}")
