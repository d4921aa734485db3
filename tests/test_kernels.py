"""Per-kernel correctness: interpret-mode pallas_call vs pure-jnp oracle,
swept over shapes / dtypes / block sizes (deliverable c).  On the CPU
backend ``interpret=None`` resolves to the interpreter."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:        # hypothesis optional in this container
    HAVE_HYPOTHESIS = False

from repro.kernels.flash_attention import ops as fa_ops
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.mlstm_chunk import ops as ml_ops
from repro.kernels.mlstm_chunk.ref import mlstm_ref
from repro.kernels.moe_gmm import ops as gmm_ops
from repro.kernels.moe_gmm.ref import moe_gmm_ref
from repro.kernels.rmsnorm import ops as rms_ops
from repro.kernels.rmsnorm.ref import rmsnorm_ref
from repro.kernels.ssd_scan import ops as ssd_ops
from repro.kernels.ssd_scan.ref import ssd_scan_ref

RNG = np.random.default_rng(0)


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 \
        else dict(rtol=2e-4, atol=2e-4)


# -- flash attention ---------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,Sq,Skv,H,KVH,Dh,causal,window,qb,kb", [
    (2, 128, 128, 4, 2, 32, True, None, 64, 64),
    (1, 256, 256, 3, 1, 16, True, 96, 64, 128),     # SWA + MHA-of-3
    (2, 128, 256, 4, 4, 64, False, None, 128, 128),  # cross-attn shape
    (1, 512, 512, 8, 2, 128, True, None, 128, 256),  # MXU-aligned
])
def test_flash_attention_kernel(dtype, B, Sq, Skv, H, KVH, Dh, causal,
                                window, qb, kb):
    q = jnp.asarray(RNG.normal(size=(B, Sq, H, Dh)), dtype)
    k = jnp.asarray(RNG.normal(size=(B, Skv, KVH, Dh)), dtype)
    v = jnp.asarray(RNG.normal(size=(B, Skv, KVH, Dh)), dtype)
    got = fa_ops.mha(q, k, v, causal=causal, window=window,
                     q_block=qb, kv_block=kb)
    G = H // KVH
    qr = q.reshape(B, Sq, KVH, G, Dh).transpose(0, 2, 3, 1, 4) \
        .reshape(B * KVH, G, Sq, Dh)
    kr = k.transpose(0, 2, 1, 3).reshape(B * KVH, Skv, Dh)
    vr = v.transpose(0, 2, 1, 3).reshape(B * KVH, Skv, Dh)
    want = attention_ref(qr, kr, vr, causal=causal, window=window)
    want = want.reshape(B, KVH, G, Sq, Dh).transpose(0, 3, 1, 2, 4) \
        .reshape(B, Sq, H, Dh)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        **_tol(dtype))


# -- ssd scan -----------------------------------------------------------------

@pytest.mark.parametrize("B,S,Din,N,chunk,dblk", [
    (2, 64, 16, 4, 16, 8),
    (1, 128, 32, 8, 32, 32),
    (2, 96, 24, 16, 48, 12),
])
def test_ssd_scan_kernel(B, S, Din, N, chunk, dblk):
    x = jnp.asarray(RNG.normal(size=(B, S, Din)), jnp.float32)
    dt = jnp.asarray(RNG.uniform(0.01, 0.2, size=(B, S, Din)), jnp.float32)
    A = -jnp.asarray(RNG.uniform(0.5, 2.0, size=(Din, N)), jnp.float32)
    Bm = jnp.asarray(RNG.normal(size=(B, S, N)), jnp.float32)
    Cm = jnp.asarray(RNG.normal(size=(B, S, N)), jnp.float32)
    got = ssd_ops.ssd_scan(x, dt, A, Bm, Cm, chunk=chunk, d_block=dblk)
    want = ssd_scan_ref(x, dt, A, Bm, Cm)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


# -- mlstm chunk ---------------------------------------------------------------

@pytest.mark.parametrize("B,S,H,Dh,chunk", [
    (2, 32, 2, 16, 8),
    (1, 64, 4, 32, 16),
    (2, 48, 1, 8, 48),     # single chunk == full parallel form
])
def test_mlstm_chunk_kernel(B, S, H, Dh, chunk):
    q = jnp.asarray(RNG.normal(size=(B, S, H, Dh)), jnp.float32)
    k = jnp.asarray(RNG.normal(size=(B, S, H, Dh)), jnp.float32)
    v = jnp.asarray(RNG.normal(size=(B, S, H, Dh)), jnp.float32)
    i_pre = jnp.asarray(RNG.normal(size=(B, S, H)), jnp.float32)
    f_pre = jnp.asarray(RNG.normal(size=(B, S, H)) + 2.0, jnp.float32)
    got = ml_ops.mlstm_chunk(q, k, v, i_pre, f_pre, chunk=chunk)

    def tok(x):
        return x.transpose(0, 2, 1, 3).reshape(B * H, S, Dh)
    want = mlstm_ref(tok(q), tok(k), tok(v),
                     i_pre.transpose(0, 2, 1).reshape(B * H, S),
                     f_pre.transpose(0, 2, 1).reshape(B * H, S))
    want = want.reshape(B, H, S, Dh).transpose(0, 2, 1, 3).reshape(
        B, S, H * Dh)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-3, atol=2e-3)


# -- moe gmm --------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("E,C,D,F,cb,fb,db", [
    (4, 32, 64, 128, 16, 64, 32),
    (8, 64, 32, 64, 64, 64, 32),
])
def test_moe_gmm_kernel(dtype, E, C, D, F, cb, fb, db):
    x = jnp.asarray(RNG.normal(size=(E, C, D)), dtype)
    w = jnp.asarray(RNG.normal(size=(E, D, F)) * 0.1, dtype)
    gs = jnp.asarray(RNG.integers(0, C + 1, size=(E,)), jnp.int32)
    got = gmm_ops.moe_gmm(x, w, gs, c_block=cb, f_block=fb, d_block=db)
    want = moe_gmm_ref(x, w, gs)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        **_tol(dtype))


# -- regressions for the TPU-compilable layouts --------------------------------

def test_moe_gmm_unequal_groups_scalar_prefetch():
    """group_sizes is scalar-prefetched: each expert must mask with its
    own count, across several row blocks per expert."""
    from repro.kernels.moe_gmm.kernel import moe_gmm
    E, C, D, F = 4, 64, 32, 64
    x = jnp.asarray(RNG.normal(size=(E, C, D)), jnp.float32)
    w = jnp.asarray(RNG.normal(size=(E, D, F)) * 0.1, jnp.float32)
    gs = jnp.asarray([0, 5, 64, 37], jnp.int32)
    got = moe_gmm(x, w, gs, c_block=16, f_block=32, d_block=16)
    want = moe_gmm_ref(x, w, gs)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)
    assert not np.asarray(got)[0].any() and not np.asarray(got)[1, 5:].any()


def test_mlstm_gate_rows_per_head():
    """Gates are (BH, 1, S) blocks: every batch·head row must read its
    own gates (rows differ strongly), across several chunks."""
    from repro.kernels.mlstm_chunk.kernel import mlstm_chunk
    BH, S, Dh = 6, 48, 8
    q, k, v = (jnp.asarray(RNG.normal(size=(BH, S, Dh)), jnp.float32)
               for _ in range(3))
    row = jnp.arange(BH, dtype=jnp.float32)[:, None]
    i_pre = jnp.asarray(RNG.normal(size=(BH, S)), jnp.float32) + row - 3
    f_pre = jnp.asarray(RNG.normal(size=(BH, S)), jnp.float32) + 2 - row
    got = mlstm_chunk(q, k, v, i_pre, f_pre, chunk=16)
    want = mlstm_ref(q, k, v, i_pre, f_pre)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-3, atol=2e-3)


def test_ssd_scan_transposed_state_blocks():
    """Transposed state and per-step ref indexing: several batch rows,
    channel blocks and chunks, with the state carried across chunks."""
    from repro.kernels.ssd_scan.kernel import ssd_scan
    B, S, Din, N = 3, 40, 24, 5
    x = jnp.asarray(RNG.normal(size=(B, S, Din)), jnp.float32)
    dt = jnp.asarray(RNG.uniform(0.01, 0.3, size=(B, S, Din)), jnp.float32)
    A = -jnp.asarray(RNG.uniform(0.2, 2.0, size=(Din, N)), jnp.float32)
    Bm = jnp.asarray(RNG.normal(size=(B, S, N)), jnp.float32)
    Cm = jnp.asarray(RNG.normal(size=(B, S, N)), jnp.float32)
    got = ssd_scan(x, dt, A, Bm, Cm, chunk=8, d_block=8)
    want = ssd_scan_ref(x, dt, A, Bm, Cm)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


def test_interpret_resolves_by_backend():
    from repro.kernels import resolve_interpret
    assert resolve_interpret(None) == (jax.default_backend() == "cpu")
    assert resolve_interpret(False) is False
    assert resolve_interpret(True) is True


# -- rmsnorm --------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("R,D,rb", [(64, 128, 16), (32, 96, 32)])
def test_rmsnorm_kernel(dtype, R, D, rb):
    x = jnp.asarray(RNG.normal(size=(R, D)), dtype)
    s = jnp.asarray(RNG.normal(size=(D,)) + 1.0, jnp.float32)
    got = rms_ops.rmsnorm(x, s, row_block=rb)
    want = rmsnorm_ref(x, s)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        **_tol(dtype))


# -- property-based sweeps (hypothesis) -----------------------------------------

if HAVE_HYPOTHESIS:
    @given(
        b=st.integers(1, 3), nq=st.integers(1, 4), nk=st.integers(1, 4),
        kvh=st.sampled_from([1, 2]), g=st.sampled_from([1, 2, 3]),
        dh=st.sampled_from([8, 16]), causal=st.booleans(),
    )
    @settings(max_examples=15, deadline=None)
    def test_flash_attention_property(b, nq, nk, kvh, g, dh, causal):
        Sq, Skv = nq * 32, nk * 32
        if causal and Skv < Sq:
            Skv = Sq
        H = kvh * g
        rng = np.random.default_rng(b * 1000 + nq * 100 + nk)
        q = jnp.asarray(rng.normal(size=(b, Sq, H, dh)), jnp.float32)
        k = jnp.asarray(rng.normal(size=(b, Skv, kvh, dh)), jnp.float32)
        v = jnp.asarray(rng.normal(size=(b, Skv, kvh, dh)), jnp.float32)
        got = fa_ops.mha(q, k, v, causal=causal, q_block=32, kv_block=32)
        qr = q.reshape(b, Sq, kvh, g, dh).transpose(0, 2, 3, 1, 4) \
            .reshape(b * kvh, g, Sq, dh)
        kr = k.transpose(0, 2, 1, 3).reshape(b * kvh, Skv, dh)
        vr = v.transpose(0, 2, 1, 3).reshape(b * kvh, Skv, dh)
        want = attention_ref(qr, kr, vr, causal=causal).reshape(
            b, kvh, g, Sq, dh).transpose(0, 3, 1, 2, 4).reshape(
            b, Sq, H, dh)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=3e-4, atol=3e-4)

    @given(n=st.integers(1, 6), din=st.sampled_from([8, 16]),
           nstate=st.sampled_from([2, 4]))
    @settings(max_examples=10, deadline=None)
    def test_ssd_scan_property(n, din, nstate):
        B, S = 1, n * 16
        rng = np.random.default_rng(n)
        x = jnp.asarray(rng.normal(size=(B, S, din)), jnp.float32)
        dt = jnp.asarray(rng.uniform(0.01, 0.3, size=(B, S, din)),
                         jnp.float32)
        A = -jnp.asarray(rng.uniform(0.3, 2.0, size=(din, nstate)),
                         jnp.float32)
        Bm = jnp.asarray(rng.normal(size=(B, S, nstate)), jnp.float32)
        Cm = jnp.asarray(rng.normal(size=(B, S, nstate)), jnp.float32)
        got = ssd_ops.ssd_scan(x, dt, A, Bm, Cm, chunk=16, d_block=din)
        want = ssd_scan_ref(x, dt, A, Bm, Cm)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-4, atol=1e-4)
