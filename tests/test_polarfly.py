"""ER_q construction invariants (paper §IV) incl. prime powers."""
from math import comb

import numpy as np
import pytest

from repro.core.gf import GF
from repro.core.metrics import diameter_and_aspl, triangle_census
from repro.core.polarfly import build_polarfly, moore_bound, moore_efficiency
from repro.core.routing import all_pairs_distances

ODD_QS = [3, 5, 7, 9, 11, 13]


@pytest.mark.parametrize("q", ODD_QS + [4, 8])
def test_basic_invariants(q):
    pf = build_polarfly(q)
    g = pf.graph
    g.validate()
    assert g.n == q * q + q + 1
    assert g.max_degree == q + 1
    diam, aspl = diameter_and_aspl(g)
    assert diam == 2
    assert aspl < 2


@pytest.mark.parametrize("q", ODD_QS)
def test_vertex_taxonomy(q):
    pf = build_polarfly(q)
    assert pf.quadric_mask.sum() == q + 1
    assert pf.v1_mask.sum() == q * (q + 1) // 2
    assert pf.v2_mask.sum() == q * (q - 1) // 2
    # quadrics have degree q (self-loop removed), others q+1
    degs = pf.graph.degrees
    assert (degs[pf.quadric_mask] == q).all()
    assert (degs[~pf.quadric_mask] == q + 1).all()


@pytest.mark.parametrize("q", [5, 7, 9])
def test_property_1(q):
    """Paper Property 1 (Bachraty & Siran)."""
    pf = build_polarfly(q)
    g, W, V1, V2 = pf.graph, pf.quadric_mask, pf.v1_mask, pf.v2_mask
    adj = g.adjacency
    # 1.1 quadrics form an independent set, each adjacent to q V1 vertices
    assert not adj[np.ix_(W, W)].any()
    assert (adj[np.ix_(W, V1)].sum(axis=1) == q).all()
    # 1.2 every V1 vertex: 2 quadrics, (q-1)/2 each in V1 and V2
    assert (adj[np.ix_(V1, W)].sum(axis=1) == 2).all()
    assert (adj[np.ix_(V1, V1)].sum(axis=1) == (q - 1) // 2).all()
    assert (adj[np.ix_(V1, V2)].sum(axis=1) == (q - 1) // 2).all()
    # 1.3 every V2 vertex: (q+1)/2 each in V1 and V2
    assert (adj[np.ix_(V2, V1)].sum(axis=1) == (q + 1) // 2).all()
    assert (adj[np.ix_(V2, V2)].sum(axis=1) == (q + 1) // 2).all()
    # 1.4 unique 2-hop path between every pair (counting quadric self-loops)
    a = adj.astype(np.int64)
    two = a @ a
    selfloop = np.diag(W.astype(np.int64))
    two_fixed = two + selfloop @ a + a @ selfloop
    off = ~np.eye(g.n, dtype=bool)
    assert (two_fixed[off] >= 1).all()
    # non-adjacent pairs: exactly one 2-hop path
    nonadj = off & ~adj
    assert (two_fixed[nonadj] == 1).all()


@pytest.mark.parametrize("q", ODD_QS)
def test_triangle_count_and_no_quadrangles(q):
    pf = build_polarfly(q)
    assert triangle_census(pf.graph) == comb(q + 1, 3)
    # no quadrangles: for adjacent pairs, exactly one common neighbor
    a = pf.graph.adjacency.astype(np.int64)
    two = a @ a
    adj_off = pf.graph.adjacency & ~np.eye(pf.n, dtype=bool)
    # common neighbors of adjacent non-quadric pairs == 1 (unique triangle)
    nq = ~pf.quadric_mask
    pairs = adj_off & nq[:, None] & nq[None, :]
    assert (two[pairs] <= 1).all()


def test_moore_efficiency_96_percent():
    """Paper abstract: >96% of Moore bound at moderate radix (q=31 -> k=32)."""
    pf = build_polarfly(31)
    eff = moore_efficiency(pf.n, 32)
    assert eff > 0.96
    assert moore_bound(32, 2) == 1 + 32 * 31 + 32  # 1 + k + k(k-1)


def test_paper_intermediate_example():
    """ER_3 worked example from §IV-D."""
    pf = build_polarfly(3)
    s = pf.vertex_id([0, 0, 1])
    d = pf.vertex_id([1, 2, 2])
    assert tuple(pf.vertices[pf.intermediate(s, d)]) == (1, 1, 0)


def _er_q_by_definition(q):
    """ER_q from its definition: all-pairs GF(q) dot products, v ~ w iff
    v . w == 0, with a quadric's self-loop dropped."""
    gf = GF(q)
    vt = np.array([(0, 0, 1)] + [(0, 1, z) for z in range(q)]
                  + [(1, y, z) for y in range(q) for z in range(q)],
                  dtype=np.int32)
    dots = gf.dot3(vt[:, None, :], vt[None, :, :])
    quadric = np.diag(dots) == 0
    neighbors = [np.flatnonzero((row == 0) & (np.arange(len(vt)) != i))
                 .astype(np.int32) for i, row in enumerate(dots)]
    v1 = (dots[quadric] == 0).any(axis=0) & ~quadric
    index = {tuple(int(x) for x in v): i for i, v in enumerate(vt)}
    return vt, neighbors, quadric, v1, ~(quadric | v1), index


@pytest.mark.parametrize("q", [2, 3, 4, 5, 7, 8, 9, 11, 13, 16, 25, 27, 31,
                               32, 49])
def test_polar_lines_match_the_all_pairs_definition(q):
    vt, neighbors, quadric, v1, v2, index = _er_q_by_definition(q)
    pf = build_polarfly(q)
    np.testing.assert_array_equal(pf.vertices, vt)
    assert len(pf.graph.neighbors) == len(neighbors)
    for got, want in zip(pf.graph.neighbors, neighbors):
        assert got.dtype == np.int32
        np.testing.assert_array_equal(got, want)
    for got, want in [(pf.quadric_mask, quadric), (pf.v1_mask, v1),
                      (pf.v2_mask, v2)]:
        assert got.dtype == bool
        np.testing.assert_array_equal(got, want)
    assert pf.index == index


@pytest.mark.parametrize("q", [79, 157])
def test_polar_lines_at_scale(q):
    """Each row is its vertex's polar line: q + 1 distinct points v^⊥,
    the vertex itself among them iff it is a quadric (then dropped)."""
    pf = build_polarfly(q)
    g, vt, gf = pf.graph, pf.vertices, pf.gf
    assert g.n == q * q + q + 1
    g.validate()  # sorted, distinct, no self loops, symmetric
    indptr, indices = g.csr
    rows = np.repeat(np.arange(g.n), g.degrees)
    assert (gf.dot3(vt[rows], vt[indices]) == 0).all()
    on_own_line = gf.dot3(vt, vt) == 0
    np.testing.assert_array_equal(pf.quadric_mask, on_own_line)
    assert on_own_line.sum() == q + 1
    np.testing.assert_array_equal(g.degrees + on_own_line, q + 1)
    assert (g.degrees[pf.quadric_mask] == q).all()
    assert (g.degrees[~pf.quadric_mask] == q + 1).all()
