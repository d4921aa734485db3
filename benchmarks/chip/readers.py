"""Arithmetic the per-layer metric readers share.  Each reader in
``metrics/<name>.py`` picks its quantity; a reader that finds nothing to
read returns ``None`` and the metric is left out of the line."""
from __future__ import annotations

import harness


def costs(facts: dict):
    return harness.load_module("costs", facts["spec"].family)


def peak(facts: dict) -> dict:
    return harness.peaks(facts["device"]["kind"])


def prefill_share(facts: dict) -> float | None:
    """Host time in prefill side steps over the window, in %."""
    if "prefill_s" not in facts:
        return None
    return 100.0 * facts["prefill_s"] / facts["window_s"]


def host_share(facts: dict) -> float | None:
    """Batcher wall time outside its prefill and decode spans, over its
    wall time, in %: sampling and bookkeeping between steps."""
    run = facts.get("run")
    if not run or not run["wall_s"]:
        return None
    rest = run["wall_s"] - run["prefill_s"] - run["decode_s"]
    return 100.0 * rest / run["wall_s"]


def idle_share(facts: dict) -> float | None:
    """Device idle share of the traced stretch while the system had work
    (a server inside its batcher's ``run()``; a train job throughout),
    in %: host gaps between programs, not waits for arrivals."""
    tr = facts.get("trace")
    if tr is None or tr.idle_work_share is None:
        return None
    return 100.0 * tr.idle_work_share


def program_ms(facts: dict, program: str) -> float | None:
    """Device milliseconds per run of ``program`` in the traced window."""
    tr = facts.get("trace")
    if tr is None:
        return None
    secs, runs = tr.program(program)
    return 1e3 * secs / runs if runs else None


DECODE = "jit_decode_step"
TRAIN = "jit_train_step"


def decode_roofline(facts: dict) -> float | None:
    """Least time the decode steps of the traced window could take on
    their bytes, over their device time, in %."""
    tr, traced = facts.get("trace"), facts.get("traced")
    if tr is None or traced is None:
        return None
    secs, runs = tr.program(DECODE)
    if not runs:
        return None
    need = costs(facts).decode_bytes(facts["spec"], runs,
                                     traced["positions"])
    return 100.0 * need / peak(facts)["hbm_bytes_per_s"] / secs


def serve_mfu(facts: dict) -> float | None:
    """Operations the traced window's decoded tokens and prefilled
    prompts need, over its length times the chip's peak, in %."""
    traced = facts.get("traced")
    if traced is None:
        return None
    c, spec = costs(facts), facts["spec"]
    flops = c.decode_tokens_flops(spec, traced["positions"]) + sum(
        c.prefill_flops(spec, n) for n in traced["prompts"])
    return 100.0 * flops / (traced["seconds"] * peak(facts)["bf16_flops"])


def train_mfu(facts: dict) -> float | None:
    """Model operations per trained token times tokens per second, over
    the peak of the cell's chips, in %."""
    if "train_tok_s" not in facts or facts.get("trace") is None:
        return None
    f = costs(facts).train_flops_per_token(facts["spec"], facts["seq"])
    chips = facts["device"]["count"]
    return 100.0 * f * facts["train_tok_s"] / (
        chips * peak(facts)["bf16_flops"])


#: Collective operations, and the ``-start``/``-done`` halves of each.
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
               "collective-permute", "all-to-all")


def collective_share(facts: dict, program: str) -> float | None:
    """Device seconds of collective operations in ``program`` over its
    device seconds, averaged over the chips, in %."""
    tr = facts.get("trace")
    if tr is None:
        return None
    secs, runs = tr.program(program)
    if not runs:
        return None
    coll = sum(s for op, s in tr.ops_of(program).items()
               if op.startswith(COLLECTIVES))
    return 100.0 * coll / secs
