"""The five kernels at the widths of the models that use them.

One :class:`KernelCase` per kernel: the kernel entry point (kernel
layout), its ``ref.py`` oracle, input shapes read from the config whose
widths they are, a seeded input maker, and the tolerances
``tests/test_kernels.py`` holds the kernel to.  ``chip_smoke.py`` runs
them compiled on a chip against their oracles;
``tests/test_chip_compile.py`` compiles them for a described v5e.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Callable

import jax
import jax.numpy as jnp

from ..configs import get_config
from .flash_attention.kernel import flash_attention
from .flash_attention.ref import attention_ref
from .mlstm_chunk.kernel import mlstm_chunk
from .mlstm_chunk.ref import mlstm_ref
from .moe_gmm.kernel import moe_gmm
from .moe_gmm.ref import moe_gmm_ref
from .rmsnorm.kernel import rmsnorm
from .rmsnorm.ref import rmsnorm_ref
from .ssd_scan.kernel import ssd_scan
from .ssd_scan.ref import ssd_scan_ref

F32, BF16 = jnp.float32, jnp.bfloat16
BF16_TOL = dict(rtol=2e-2, atol=2e-2)


@dataclass(frozen=True)
class KernelCase:
    name: str
    arch: str                       # the config whose widths these are
    run: Callable                   # (*inputs, interpret=...) -> out
    ref: Callable                   # (*inputs) -> out
    shapes: tuple[tuple[tuple[int, ...], jnp.dtype], ...]
    make: Callable[[jax.Array], tuple]   # key -> inputs
    rtol: float
    atol: float

    def specs(self, sharding=None) -> list[jax.ShapeDtypeStruct]:
        return [jax.ShapeDtypeStruct(s, d, sharding=sharding)
                for s, d in self.shapes]


def _normal(key, shape, dtype, scale=1.0, shift=0.0):
    return (jax.random.normal(key, shape, F32) * scale + shift).astype(dtype)


def _uniform(key, shape, lo, hi):
    return jax.random.uniform(key, shape, F32, lo, hi)


def _rmsnorm_case() -> KernelCase:
    cfg = get_config("smollm-360m")
    R, D = 4 * 2048, cfg.d_model           # B=4 × S=2048 rows

    def make(key):
        k1, k2 = jax.random.split(key)
        return (_normal(k1, (R, D), BF16),
                _normal(k2, (D,), F32, shift=1.0))
    return KernelCase("rmsnorm", cfg.name, rmsnorm, rmsnorm_ref,
                      (((R, D), BF16), ((D,), F32)), make, **BF16_TOL)


def _flash_case() -> KernelCase:
    cfg = get_config("smollm-360m")
    B, S, Dh = 4, 2048, cfg.resolved_head_dim
    BH, G = B * cfg.n_kv_heads, cfg.n_heads // cfg.n_kv_heads
    shapes = (((BH, G, S, Dh), BF16), ((BH, S, Dh), BF16),
              ((BH, S, Dh), BF16))

    def make(key):
        ks = jax.random.split(key, 3)
        return tuple(_normal(k, s, d) for k, (s, d) in zip(ks, shapes))
    return KernelCase("flash_attention", cfg.name, flash_attention,
                      attention_ref, shapes, make, **BF16_TOL)


def _ssd_case() -> KernelCase:
    cfg = get_config("jamba-v0.1-52b")
    mb = cfg.mamba
    B, S, Din, N = 2, 4 * mb.chunk, mb.expand * cfg.d_model, mb.d_state
    shapes = (((B, S, Din), F32), ((B, S, Din), F32), ((Din, N), F32),
              ((B, S, N), F32), ((B, S, N), F32))

    def make(key):
        ks = jax.random.split(key, 5)
        return (_normal(ks[0], (B, S, Din), F32),
                _uniform(ks[1], (B, S, Din), 0.01, 0.2),
                -_uniform(ks[2], (Din, N), 0.5, 2.0),
                _normal(ks[3], (B, S, N), F32),
                _normal(ks[4], (B, S, N), F32))

    def run(*xs, interpret=None):
        return ssd_scan(*xs, chunk=mb.chunk, interpret=interpret)
    return KernelCase("ssd_scan", cfg.name, run, ssd_scan_ref, shapes,
                      make, rtol=1e-4, atol=1e-4)


def _mlstm_case() -> KernelCase:
    cfg = get_config("xlstm-125m")
    xc = cfg.xlstm
    B, S, H = 2, 4 * xc.chunk, cfg.n_heads
    Dh = xc.proj_factor_mlstm * cfg.d_model // H
    BH = B * H
    shapes = (((BH, S, Dh), F32),) * 3 + (((BH, S), F32),) * 2

    def make(key):
        ks = jax.random.split(key, 5)
        return (*(_normal(k, (BH, S, Dh), F32) for k in ks[:3]),
                _normal(ks[3], (BH, S), F32),
                _normal(ks[4], (BH, S), F32, shift=2.0))

    def run(*xs, interpret=None):
        return mlstm_chunk(*xs, chunk=xc.chunk, interpret=interpret)
    return KernelCase("mlstm_chunk", cfg.name, run, mlstm_ref, shapes,
                      make, rtol=2e-3, atol=2e-3)


def _moe_gmm_case() -> KernelCase:
    cfg = get_config("deepseek-v3-671b")
    E, C = 8, 256                  # one chip's share of experts × capacity
    D, F = cfg.d_model, cfg.moe.d_expert
    shapes = (((E, C, D), BF16), ((E, D, F), BF16), ((E,), jnp.int32))

    def make(key):
        ks = jax.random.split(key, 3)
        return (_normal(ks[0], (E, C, D), BF16),
                _normal(ks[1], (E, D, F), BF16, scale=0.1),
                jax.random.randint(ks[2], (E,), 0, C + 1, jnp.int32))
    return KernelCase("moe_gmm", cfg.name, moe_gmm, moe_gmm_ref, shapes,
                      make, **BF16_TOL)


CASE_NAMES = ("rmsnorm", "flash_attention", "ssd_scan", "mlstm_chunk",
              "moe_gmm")
_BUILDERS = dict(zip(CASE_NAMES, (_rmsnorm_case, _flash_case, _ssd_case,
                                  _mlstm_case, _moe_gmm_case)))


def kernel_case(name: str) -> KernelCase:
    return _BUILDERS[name]()
