"""Pallas netstep kernel validation (interpret mode on CPU): shape
sweeps vs the pure-jnp oracle and the simulator's own allocator, plus
allocation invariants."""
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.simulator import _alloc_jnp
from repro.kernels.netstep.netstep import netstep_pallas
from repro.kernels.netstep.ref import netstep_ref


# ---------------------------------------------------------------------
# netstep (paper hot loop)
# ---------------------------------------------------------------------

def _alloc_inputs(rng, n, pi, v):
    op_slot = rng.integers(-1, pi, (n, pi, v)).astype(np.int32)
    eligible = (rng.uniform(size=(n, pi, v)) < 0.5) & (op_slot >= 0)
    return jnp.asarray(op_slot), jnp.asarray(eligible)


# after the odd shapes, the padded (N, PI = radix + 1, V) of each runner
# the chip benchmark's cells compile: N=64 at radix 3, 4, 6, 8 and 14
# (Table III), N=256 at radix 6
@pytest.mark.parametrize("n,pi,v", [(16, 5, 4), (100, 7, 4), (64, 31, 2),
                                    (37, 31, 4),
                                    (64, 4, 4), (64, 5, 4), (64, 7, 4),
                                    (64, 9, 4), (64, 15, 4), (256, 7, 4)])
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
