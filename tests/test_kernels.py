"""Pallas kernel validation (interpret mode on CPU): shape/dtype sweeps
vs the pure-jnp oracles, plus allocation invariants for netstep."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels.flash_attention import ops as fa_ops
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.flash_attention.flash_attention import \
    flash_attention_bhsd
from repro.kernels.ssd_scan.ssd_scan import ssd_scan_pallas
from repro.kernels.ssd_scan.ref import ssd_ref, ssd_naive
from repro.core.simulator import _alloc_jnp
from repro.kernels.netstep.netstep import netstep_pallas
from repro.kernels.netstep.ref import netstep_ref
from repro.models.ssm import ssd_chunked_core


# ---------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("tq,tk,causal,window", [
    (128, 128, True, None),
    (256, 256, True, None),
    (128, 256, False, None),
    (256, 256, True, 128),
    (128, 128, True, 64),
])
def test_flash_attention_matches_ref(tq, tk, causal, window, dtype):
    rng = np.random.default_rng(0)
    bh, hd = 3, 128
    q = jnp.asarray(rng.normal(0, 1, (bh, tq, hd)), dtype)
    k = jnp.asarray(rng.normal(0, 1, (bh, tk, hd)), dtype)
    v = jnp.asarray(rng.normal(0, 1, (bh, tk, hd)), dtype)
    got = flash_attention_bhsd(q, k, v, causal=causal, window=window,
                               interpret=True)
    want = attention_ref(q, k, v, causal=causal, window=window)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               atol=tol, rtol=tol)


def test_flash_attention_gqa_wrapper():
    rng = np.random.default_rng(1)
    b, t, h, kv, hd = 2, 128, 4, 2, 128
    q = jnp.asarray(rng.normal(0, 1, (b, t, h, hd)), jnp.float32)
    k = jnp.asarray(rng.normal(0, 1, (b, t, kv, hd)), jnp.float32)
    v = jnp.asarray(rng.normal(0, 1, (b, t, kv, hd)), jnp.float32)
    got = fa_ops.flash_attention(q, k, v, causal=True)
    # oracle via broadcast + ref
    kr = jnp.repeat(k, h // kv, 2)
    vr = jnp.repeat(v, h // kv, 2)
    qb = q.transpose(0, 2, 1, 3).reshape(b * h, t, hd)
    kb = kr.transpose(0, 2, 1, 3).reshape(b * h, t, hd)
    vb = vr.transpose(0, 2, 1, 3).reshape(b * h, t, hd)
    want = attention_ref(qb, kb, vb, causal=True) \
        .reshape(b, h, t, hd).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=3e-5, rtol=3e-5)


# ---------------------------------------------------------------------
# SSD scan
# ---------------------------------------------------------------------

def _ssd_inputs(rng, b, t, h, p, n, dtype=jnp.float32):
    return (jnp.asarray(rng.normal(0, 1, (b, t, h, p)), dtype),
            jnp.asarray(rng.uniform(0.05, 0.9, (b, t, h)), jnp.float32),
            -jnp.asarray(rng.uniform(0.3, 2.0, (h,)), jnp.float32),
            jnp.asarray(rng.normal(0, 1, (b, t, n)), dtype),
            jnp.asarray(rng.normal(0, 1, (b, t, n)), dtype))


def test_ssd_chunked_core_matches_naive():
    rng = np.random.default_rng(2)
    x, dt, a, bm, cm = _ssd_inputs(rng, 2, 32, 3, 4, 5)
    for chunk in (4, 8, 16, 32):
        y, s = ssd_chunked_core(x, dt, a, bm, cm, chunk)
        yn, sn = ssd_naive(x, dt, a, bm, cm)
        np.testing.assert_allclose(np.asarray(y), np.asarray(yn),
                                   atol=2e-5, rtol=2e-4)
        np.testing.assert_allclose(np.asarray(s), np.asarray(sn),
                                   atol=2e-5, rtol=2e-4)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,t,h,p,n,chunk", [
    (2, 64, 4, 8, 16, 16),
    (1, 128, 2, 16, 8, 32),
    (3, 32, 8, 4, 4, 8),
])
def test_ssd_kernel_matches_ref(b, t, h, p, n, chunk, dtype):
    rng = np.random.default_rng(3)
    x, dt, a, bm, cm = _ssd_inputs(rng, b, t, h, p, n, dtype)
    y, s = ssd_scan_pallas(x, dt, a, bm, cm, chunk=chunk, interpret=True)
    yr, sr = ssd_ref(x, dt, a, bm, cm, chunk)
    tol = 3e-2 if dtype == jnp.bfloat16 else 1e-4
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(yr, np.float32),
                               atol=tol, rtol=tol)
    np.testing.assert_allclose(np.asarray(s), np.asarray(sr),
                               atol=tol, rtol=tol)


@given(seed=st.integers(0, 1000))
@settings(max_examples=8, deadline=None)
def test_ssd_kernel_property(seed):
    rng = np.random.default_rng(seed)
    b = int(rng.integers(1, 3))
    nc = int(rng.integers(1, 4))
    chunk = int(rng.choice([4, 8]))
    h, p, n = (int(rng.integers(1, 5)), int(rng.choice([4, 8])),
               int(rng.choice([4, 8])))
    x, dt, a, bm, cm = _ssd_inputs(rng, b, nc * chunk, h, p, n)
    y, s = ssd_scan_pallas(x, dt, a, bm, cm, chunk=chunk, interpret=True)
    yn, sn = ssd_naive(x, dt, a, bm, cm)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yn),
                               atol=1e-4, rtol=1e-3)


# ---------------------------------------------------------------------
# netstep (paper hot loop)
# ---------------------------------------------------------------------

def _alloc_inputs(rng, n, pi, v):
    op_slot = rng.integers(-1, pi, (n, pi, v)).astype(np.int32)
    eligible = (rng.uniform(size=(n, pi, v)) < 0.5) & (op_slot >= 0)
    return jnp.asarray(op_slot), jnp.asarray(eligible)


@pytest.mark.parametrize("n,pi,v", [(16, 5, 4), (100, 7, 4), (64, 31, 2),
                                    (37, 31, 4)])
def test_netstep_matches_ref(n, pi, v):
    """Bitwise equal to the oracle and to the simulator's own jnp
    allocator, for one shared counter and for split (vc, port) ones."""
    rng = np.random.default_rng(4)
    op_slot, eligible = _alloc_inputs(rng, n, pi, v)
    for rr in (0, 3, 11, (1, 29), (3, 0)):
        got = netstep_pallas(op_slot, eligible, rr, interpret=True)
        want = netstep_ref(op_slot, eligible, rr)
        rr_vc, rr_port = rr if isinstance(rr, tuple) else (rr, rr)
        jnp_alloc = _alloc_jnp(op_slot, eligible, rr_vc, rr_port)
        for g, w, j in zip(got, want, jnp_alloc):
            assert g.dtype == w.dtype
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
            np.testing.assert_array_equal(np.asarray(g), np.asarray(j))


@given(seed=st.integers(0, 10_000))
@settings(max_examples=20, deadline=None)
def test_netstep_allocation_invariants(seed):
    rng = np.random.default_rng(seed)
    n, pi, v = int(rng.integers(4, 40)), int(rng.integers(2, 9)), 4
    op_slot, eligible = _alloc_inputs(rng, n, pi, v)
    win, vc, req = netstep_pallas(op_slot, eligible, 2, interpret=True)
    win = np.asarray(win)
    # at most one winning VC per input port
    assert (win.sum(axis=2) <= 1).all()
    # winners were eligible
    assert (win <= np.asarray(eligible)).all()
    # at most one winner per (router, output slot)
    slots = np.asarray(op_slot)
    for o in range(pi):
        cnt = ((slots == o) & win).sum(axis=(1, 2))
        assert (cnt <= 1).all()
