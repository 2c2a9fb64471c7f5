"""Hypothesis property tests on the system's invariants."""
import numpy as np
import jax.numpy as jnp
import pytest

pytest.importorskip("hypothesis", reason="dev-only dep; see requirements-dev.txt")
from hypothesis import given, settings, strategies as st

from repro.graphs import erdos_renyi
from repro.graphs.structure import Graph
from repro.graphs.partition import partition_2d
from repro.core import (Activity, heterogeneous, build_operators, power_psi,
                        dense_operators, exact_psi)

graph_params = st.tuples(st.integers(10, 120), st.integers(0, 400),
                         st.integers(0, 10_000))


def _mk_graph(n, m, seed):
    m = min(m, n * (n - 1) // 2)
    return erdos_renyi(n, max(1, m), seed=seed)


@given(graph_params)
@settings(max_examples=20, deadline=None)
def test_a_is_substochastic(params):
    """Row sums of A are in [0, 1] — the convergence precondition (§III-B)."""
    n, m, seed = params
    g = _mk_graph(n, m, seed)
    act = heterogeneous(n, seed=seed + 1)
    A, B, c, d = dense_operators(g, act)
    rows = A.sum(axis=1)
    assert np.all(rows <= 1.0 + 1e-9)
    assert np.all(rows >= 0.0)
    # A + B row sums == 1 exactly on rows with leaders
    has = g.out_degree > 0
    np.testing.assert_allclose((A + B).sum(axis=1)[has], 1.0, rtol=1e-9)


@given(graph_params)
@settings(max_examples=15, deadline=None)
def test_psi_bounds_and_agreement(params):
    """ψ ∈ (0, 1]·(1/N)·N = (0, 1]; Power-ψ matches the exact solve."""
    n, m, seed = params
    g = _mk_graph(n, m, seed)
    act = heterogeneous(n, seed=seed + 2)
    ops = build_operators(g, act)
    res = power_psi(ops, tol=1e-11, max_iter=5000)
    psi = np.asarray(res.psi)
    assert np.all(psi >= 0.0) and np.all(psi <= 1.0)
    psi_true, _ = exact_psi(g, act)
    assert np.abs(psi - psi_true).max() < 1e-4


@given(graph_params)
@settings(max_examples=15, deadline=None)
def test_q_columns_are_distributions(params):
    """Σ_i q_i^{(n)} = 1 per wall n (the OSP model conservation law):
    column sums of Q = C·P + D equal 1 for nodes with λ+μ > 0."""
    n, m, seed = params
    g = _mk_graph(n, m, seed)
    act = heterogeneous(n, seed=seed + 3)
    A, B, c, d = dense_operators(g, act)
    P = np.linalg.solve(np.eye(n) - A, B)
    Q = c[:, None] * P + np.diag(d)
    # rows of Q here: Q[n_, i] = q_i^{(n_)}; conservation: Σ_i q_i^{(n)} ≤ 1
    sums = Q.sum(axis=1)
    assert np.all(sums <= 1.0 + 1e-6)


@given(st.integers(20, 400), st.integers(1, 12), st.integers(0, 99))
@settings(max_examples=20, deadline=None)
def test_partition_layout_roundtrip(n, dm, seed):
    """to_src_layout / from_src_layout are exact inverses."""
    d = 1 + dm % 4
    mo = 1 + (dm // 4) % 3
    g = _mk_graph(n, 3 * n, seed)
    part = partition_2d(g, d, mo)
    rng = np.random.default_rng(seed)
    v = rng.normal(size=n).astype(np.float32)
    round_trip = part.from_src_layout(part.to_src_layout(v))
    np.testing.assert_array_equal(round_trip, v)
    # piece layout reshape equals src layout (the psum_scatter identity)
    pieces = part.to_piece_layout(v)
    np.testing.assert_array_equal(pieces.reshape(part.d, -1),
                                  part.to_src_layout(v))


@given(st.integers(10, 200), st.integers(5, 600), st.integers(0, 99))
@settings(max_examples=20, deadline=None)
def test_partition_covers_all_edges(n, m, seed):
    g = _mk_graph(n, m, seed)
    part = partition_2d(g, 2, 2)
    assert int(part.e_counts.sum()) == g.m
    # every real edge appears exactly once with valid local ids
    cnt = (part.src_local < part.local_src_n).sum()
    assert cnt == g.m


@given(st.integers(2, 50), st.integers(0, 99))
@settings(max_examples=20, deadline=None)
def test_generator_properties(n, seed):
    m = min(3 * n, n * (n - 1) // 2)
    g = erdos_renyi(n, m, seed=seed)
    assert g.m == m
    assert not np.any(g.src == g.dst)           # no self loops
    key = g.src.astype(np.int64) * g.n + g.dst
    assert np.unique(key).size == g.m           # no duplicate edges


@given(st.integers(1, 300), st.integers(0, 2_000), st.integers(0, 10_000))
@settings(max_examples=20, deadline=None)
def test_sorted_views_are_the_stable_orders(n, m, seed):
    """Both sorted edge views list the edges in a stable argsort's order,
    ties (many edges to one user) in their input order."""
    rng = np.random.default_rng(seed)
    src, dst = rng.integers(0, n, (2, m)).astype(np.int32)
    g = Graph(n, src, dst)
    for (s, d), key in ((g.edges_by_dst, dst), (g.edges_by_src, src)):
        o = np.argsort(key, kind="stable")
        np.testing.assert_array_equal(s, src[o])
        np.testing.assert_array_equal(d, dst[o])


@given(st.lists(st.floats(0.01, 10.0), min_size=3, max_size=30),
       st.integers(0, 999))
@settings(max_examples=20, deadline=None)
def test_activity_scale_invariance(lams, seed):
    """ψ is invariant to a global rescale of all rates (model property:
    only rate *ratios* matter)."""
    n = len(lams)
    g = _mk_graph(n, 2 * n, seed)
    rng = np.random.default_rng(seed)
    mus = rng.uniform(0.1, 2.0, n)
    a1 = Activity(np.asarray(lams), mus)
    a2 = Activity(np.asarray(lams) * 7.3, mus * 7.3)
    p1, _ = exact_psi(g, a1)
    p2, _ = exact_psi(g, a2)
    np.testing.assert_allclose(p1, p2, rtol=1e-8, atol=1e-12)
