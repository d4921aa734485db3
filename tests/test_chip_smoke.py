"""``chip_smoke.py`` off the chip: it refuses to report without a TPU, and
its phases' checks hold at smoke size on the CPU (kernels interpreted)."""
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402


def test_refuses_without_tpu(capsys):
    assert chip_smoke.main([]) != 0
    out = capsys.readouterr().out.strip().splitlines()
    assert out and '"ok"' not in out[-1]


def test_plan_problems_flag_degradations_and_lint():
    clean = {"degradations": [], "lint": {"ok": True, "issues": []}}
    assert chip_smoke.plan_problems("x", clean) == []
    bad = {"degradations": ["dse: best_uniform [boom]"],
           "lint": {"ok": False, "issues": ["fifo-underdepth"]}}
    assert len(chip_smoke.plan_problems("x", bad)) == 2
    assert chip_smoke.plan_problems("x", {"degradations": []}) == [
        "x: plan was not linted"]


def test_serve_phase_at_smoke_size(capsys):
    problems = chip_smoke.serve_phase(
        ["--arch", "smollm-360m", "--smoke", "--slots", "3",
         "--requests", "5", "--prompt-len-range", "4", "24",
         "--gen-range", "3", "8", "--temperature", "0"], n_oracle=2)
    assert problems == []
    assert capsys.readouterr().out.count("streamed == decode_offline: "
                                         "True") == 2


def test_train_phase_at_smoke_size():
    assert chip_smoke.train_phase(
        ["--arch", "smollm-360m", "--smoke", "--steps", "2", "--batch",
         "2", "--seq", "32", "--remat", "full", "--ckpt-every", "0"]) == []


def test_kernel_phase_flags_mismatch():
    import dataclasses

    import jax.numpy as jnp

    from repro.kernels.cases import kernel_case
    case = kernel_case("rmsnorm")
    small = dataclasses.replace(
        case, shapes=(((16, 128), jnp.bfloat16), ((128,), jnp.float32)),
        make=lambda key: (jnp.ones((16, 128), jnp.bfloat16),
                          jnp.ones((128,), jnp.float32)))
    assert chip_smoke.kernel_phase([small], seed=0) == []
    wrong = dataclasses.replace(small, ref=lambda x, s: x * 2)
    assert chip_smoke.kernel_phase([wrong], seed=0) != []
