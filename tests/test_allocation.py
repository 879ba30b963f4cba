"""Paper math: Lemma 1, Eq. 7/12/13/14, Theorems 5/6/7, Corollary 6.1."""
import numpy as np
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # image without hypothesis: deterministic shim (minihyp)
    from minihyp import given, settings, strategies as st

from repro.core.allocation import (
    bpcc_allocation,
    eq7_lhs,
    hcmm_allocation,
    lambda_infimum,
    lambda_supremum,
    load_balanced_allocation,
    load_infimum,
    solve_lambda,
    tau_star_infimum,
    tau_star_supremum,
    uniform_allocation,
)
from repro.core.distributions import ShiftedExp, sample_heterogeneous_cluster

WORKERS = sample_heterogeneous_cluster(10, seed=7)
R = 10_000


def test_eq7_root_is_valid():
    for w in WORKERS:
        for p in (1, 2, 7, 100):
            lam = solve_lambda(w.mu, w.alpha, p)
            assert abs(eq7_lhs(lam, w.mu, w.alpha, p) - 1.0) < 1e-8


def test_lemma1_bounds():
    """alpha = inf lambda < lambda(p) <= sup lambda = lambda(p=1)."""
    for w in WORKERS:
        sup = lambda_supremum(w.mu, w.alpha)
        inf = lambda_infimum(w.mu, w.alpha)
        assert inf < sup
        prev = sup + 1e-12
        for p in (1, 2, 4, 16, 64, 256):
            lam = solve_lambda(w.mu, w.alpha, p)
            assert inf - 1e-12 <= lam <= sup + 1e-9
            assert lam <= prev + 1e-9  # monotone nonincreasing in p
            prev = lam
        # convergence to the infimum (Lemma 1 Eq. 8)
        assert solve_lambda(w.mu, w.alpha, 100_000) == pytest.approx(w.alpha, rel=1e-3)


def test_theorem5_tau_monotone_in_p():
    taus = [bpcc_allocation(R, WORKERS, p=p).tau for p in (1, 2, 4, 8, 32, 128)]
    assert all(a >= b - 1e-9 for a, b in zip(taus, taus[1:]))


def test_theorem6_inf_sup():
    inf = tau_star_infimum(R, WORKERS)
    sup = tau_star_supremum(R, WORKERS)
    tau_p1 = bpcc_allocation(R, WORKERS, p=1).tau
    tau_big = bpcc_allocation(R, WORKERS, p=10_000).tau
    assert sup == pytest.approx(tau_p1, rel=1e-9)       # sup attained at p=1
    assert tau_big == pytest.approx(inf, rel=5e-3)      # converges to inf
    assert inf < sup


def test_corollary61_load_convergence():
    lhat = load_infimum(R, WORKERS)
    alloc = bpcc_allocation(R, WORKERS, p=10_000)
    assert np.allclose(alloc.loads, lhat, rtol=5e-3, atol=1.5)


def test_hcmm_is_bpcc_p1():
    a = hcmm_allocation(R, WORKERS)
    b = bpcc_allocation(R, WORKERS, p=1)
    assert np.array_equal(a.loads, b.loads)
    assert a.tau == pytest.approx(b.tau)


def test_theorem7_bpcc_beats_hcmm():
    assert bpcc_allocation(R, WORKERS).tau <= hcmm_allocation(R, WORKERS).tau + 1e-9


def test_uncoded_allocations_sum_to_r():
    for fn in (uniform_allocation, load_balanced_allocation):
        alloc = fn(R, WORKERS)
        assert alloc.loads.sum() == R
        assert not alloc.coded


def test_load_balanced_weights():
    alloc = load_balanced_allocation(R, WORKERS)
    w = np.array([wk.mu / (wk.mu * wk.alpha + 1) for wk in WORKERS])
    expect = R * w / w.sum()
    assert np.abs(alloc.loads - expect).max() <= 1.0


def test_p_repair_loop():
    """p > resulting load must be repaired down, not crash."""
    ws = [ShiftedExp(mu=5.0, alpha=0.2) for _ in range(4)]
    alloc = bpcc_allocation(40, ws, p=1000)  # load/worker ~ 10 << p
    assert (alloc.batches <= np.maximum(alloc.loads, 1)).all()


@settings(max_examples=30, deadline=None)
@given(
    mu=st.floats(0.5, 80.0),
    alpha=st.floats(1e-3, 2.0),
    p=st.integers(1, 300),
)
def test_lambda_properties(mu, alpha, p):
    lam = solve_lambda(mu, alpha, p)
    assert alpha - 1e-12 <= lam <= lambda_supremum(mu, alpha) * (1 + 1e-9)
    assert abs(eq7_lhs(lam, mu, alpha, p) - 1.0) < 1e-6


@settings(max_examples=20, deadline=None)
@given(
    n=st.integers(2, 12),
    seed=st.integers(0, 10_000),
    p=st.integers(1, 64),
)
def test_bpcc_allocation_properties(n, seed, p):
    ws = sample_heterogeneous_cluster(n, seed=seed)
    alloc = bpcc_allocation(5000, ws, p=p)
    assert (alloc.loads >= 1).all()
    assert alloc.tau > 0
    # total coded rows exceed r (redundancy) for any heterogeneous cluster
    assert alloc.total_rows >= 5000
    # faster workers (smaller alpha+1/mu) get >= loads of slower ones, on
    # average: check rank correlation is non-positive
    cost = np.array([w.alpha + 1 / w.mu for w in ws])
    if np.ptp(alloc.loads) == 0:
        # near-identical workers (e.g. n=2, seed=3073) get equal loads: the
        # correlation is undefined and no worker is favoured
        return
    rho = np.corrcoef(cost, alloc.loads)[0, 1]
    assert rho < 0.5  # weakly anti-correlated (noise tolerated)
