"""Sparse (blocked-BFS / CSR) graph engine vs the dense reference engine.

Bit-exactness of distances and next hops across topologies (intact and
edge-damaged), ECMP successor-table blocking, CSR edge-id lookups, the
memory-envelope block-size heuristic, the UNREACHABLE sentinel, the
vectorized Graph construction paths, and the saturation truncation-error
report.  The `large`-marked test exercises the benchmark scale tier.
"""
import numpy as np
import pytest

from repro.core import topologies as tp
from repro.core.graph import Graph, GraphBuilder, UNREACHABLE
from repro.core.metrics import bisection_fraction, diameter_and_aspl
from repro.core.polarfly import build_polarfly
from repro.core import routing as routing_mod
from repro.core.routing import (all_pairs_distances, bfs_block_size,
                                bfs_peak_bytes, build_routing,
                                distance_blocks, next_hop_table,
                                sparse_routing_tables)
from repro.simulation import (build_flow_paths, build_flow_paths_reference,
                              make_pattern, saturation_throughput,
                              truncation_error)
from repro.simulation import fluid as fluid_mod
from repro.simulation import paths as paths_mod

TOPOS = {
    "pf13": lambda: build_polarfly(13).graph,
    "sf11": lambda: tp.build_slimfly(11),
    "ps5x5": lambda: tp.build_polarstar(5, 5),
    "df": lambda: tp.build_dragonfly(6, 3),
    "ft": lambda: tp.build_fat_tree(6, 3),
    "jf": lambda: tp.build_jellyfish(120, 7, seed=0),
}

FIELDS = ("edges", "hops", "valid", "is_min", "first_edge")


def _graph(name: str, which: str) -> Graph:
    g = TOPOS[name]()
    if which == "damaged":
        g = g.subgraph_without_edges(g.edge_list[::5][:8])
    return g


# ---------------------------------------------------------------------------
# distances / next hops: sparse == dense, bit for bit
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", sorted(TOPOS))
@pytest.mark.parametrize("which", ["intact", "damaged"])
def test_sparse_dense_bit_identical(name, which):
    g = _graph(name, which)
    dd = all_pairs_distances(g, engine="dense")
    ds = all_pairs_distances(g, engine="sparse")
    assert ds.dtype == dd.dtype == np.int16
    assert np.array_equal(dd, ds)
    nh_d = next_hop_table(g, dd, engine="dense")
    d_s, nh_s = sparse_routing_tables(g)
    assert nh_s.dtype == nh_d.dtype == np.int32
    assert np.array_equal(nh_d, nh_s)
    assert np.array_equal(dd, d_s)


def test_sparse_blocking_is_invisible():
    """Any block size (including single-source) yields the same tables."""
    g = TOPOS["df"]()
    ref_d, ref_nh = sparse_routing_tables(g)
    for block in (1, 7, g.n):
        d, nh = sparse_routing_tables(g, block=block)
        assert np.array_equal(ref_d, d)
        assert np.array_equal(ref_nh, nh)


def test_build_routing_engines_agree():
    pf = build_polarfly(9)
    rt_d = build_routing(pf.graph, pf, engine="dense")
    rt_s = build_routing(pf.graph, engine="sparse")
    assert np.array_equal(rt_d.dist, rt_s.dist)
    assert np.array_equal(rt_d.next_hop, rt_s.next_hop)  # algebraic == BFS
    assert rt_d.diameter == rt_s.diameter
    with pytest.raises(ValueError, match="unknown engine"):
        build_routing(pf.graph, engine="turbo")


def test_streaming_diameter_matches_dense():
    for name in ("pf13", "df", "ft"):
        g = TOPOS[name]()
        dense = diameter_and_aspl(g, engine="dense")
        sparse = diameter_and_aspl(g, engine="sparse")
        assert dense == sparse  # exact integer sums -> identical floats


def test_unreachable_sentinel_disconnected():
    b = GraphBuilder("two-islands", 5)
    b.add_edge(0, 1)
    b.add_edge(2, 3)
    b.add_edge(3, 4)
    g = b.freeze()
    for engine in ("dense", "sparse"):
        d = all_pairs_distances(g, engine=engine)
        assert d[0, 2] == UNREACHABLE and d[4, 1] == UNREACHABLE
        nh = (next_hop_table(g, d, engine="dense") if engine == "dense"
              else sparse_routing_tables(g)[1])
        assert nh[0, 2] == UNREACHABLE and nh[0, 1] == 1
    assert diameter_and_aspl(g, engine="dense") == (int(UNREACHABLE),
                                                    float("inf"))
    assert diameter_and_aspl(g, engine="sparse") == (int(UNREACHABLE),
                                                     float("inf"))


# ---------------------------------------------------------------------------
# memory envelope of the blocked BFS
# ---------------------------------------------------------------------------

def test_bfs_block_size_memory_envelope():
    """The benchmark scale tier's distance computation fits 2 GiB: block
    size chosen by the default budget keeps working set + output tables
    under the envelope for PF(79) and PS(9,61)."""
    for n, radix in ((6321, 80), (5551, 40)):  # PF(79), PS(9, 61)
        e_dir = n * radix
        block = bfs_block_size(n, e_dir)
        assert 1 <= block <= n
        assert bfs_peak_bytes(n, e_dir, block) < 2 * 2 ** 30
        # streaming callers (no [n, n] outputs) use far less
        assert bfs_peak_bytes(n, e_dir, block, dist_table=False,
                              next_hop=False) <= routing_mod._BFS_BUDGET_BYTES
    # monotone in the budget; floor of one source under any budget
    assert bfs_block_size(6321, 6321 * 80, 2 * routing_mod._BFS_BUDGET_BYTES) \
        >= bfs_block_size(6321, 6321 * 80)
    assert bfs_block_size(6321, 6321 * 80, 1) == 1
    # tiny graphs: one block covers everything
    assert bfs_block_size(8, 24) == 8


# ---------------------------------------------------------------------------
# path construction on CSR: edge ids, ECMP blocking, sparse routing tables
# ---------------------------------------------------------------------------

def test_edge_ids_csr_matches_dense_table():
    g = TOPOS["df"]()
    de = paths_mod.build_directed_edges(g)
    u, v = np.meshgrid(np.arange(g.n), np.arange(g.n), indexing="ij")
    assert np.array_equal(de.edge_ids(u, v), de.table[u, v])
    # broadcasting forms used by the candidate builders
    src = np.arange(g.n)
    nb0 = np.array([int(nb[0]) for nb in g.neighbors])
    ids = de.edge_ids(src[:, None], nb0[:, None])
    assert ids.shape == (g.n, 1)
    assert np.array_equal(ids[:, 0], de.table[src, nb0])


def test_edge_ids_on_edge_free_graph():
    """Regression: the CSR lookup must return -1 (like the dense table did),
    not IndexError, when the graph has no edges at all."""
    g = GraphBuilder("empty", 3).freeze()
    de = paths_mod.build_directed_edges(g)
    assert de.num == 0
    out = de.edge_ids(np.array([0, 1]), np.array([1, 2]))
    assert np.array_equal(out, [-1, -1])


def test_ecmp_blocked_table_matches_unblocked(monkeypatch):
    pf = build_polarfly(7)
    rt = build_routing(pf.graph, pf)
    pat = make_pattern("uniform", rt, p=4, seed=1)
    full = build_flow_paths(rt, pat, "ecmp", k_candidates=5, seed=2)
    monkeypatch.setattr(paths_mod, "_ECMP_BLOCK_MAX_ENTRIES", 1)
    blocked = build_flow_paths(rt, pat, "ecmp", k_candidates=5, seed=2)
    ref = build_flow_paths_reference(rt, pat, "ecmp", k_candidates=5, seed=2)
    for f in FIELDS:
        assert np.array_equal(getattr(full, f), getattr(blocked, f)), f
        assert np.array_equal(getattr(full, f), getattr(ref, f)), f


@pytest.mark.parametrize("mode", ["min", "ecmp", "valiant", "cvaliant",
                                  "ugal", "ugal_pf"])
@pytest.mark.parametrize("which", ["intact", "damaged"])
def test_candidate_paths_on_sparse_routing(mode, which):
    """Both path engines agree when the routing tables come from the sparse
    engine (ECMP successor sets, Valiant segments, bounce-back filtering)."""
    g = _graph("pf13", which)
    rt = build_routing(g, engine="sparse")
    pat = make_pattern("uniform", rt, p=4, seed=3)
    vec = build_flow_paths(rt, pat, mode, k_candidates=5, seed=7)
    ref = build_flow_paths_reference(rt, pat, mode, k_candidates=5, seed=7)
    for f in FIELDS:
        assert np.array_equal(getattr(vec, f), getattr(ref, f)), (mode, f)


@pytest.mark.parametrize("name", sorted(TOPOS))
@pytest.mark.parametrize("which", ["intact", "damaged"])
def test_candidate_paths_all_topologies(name, which):
    """ECMP successor sets and UGAL_PF candidate construction stay
    engine-equivalent on sparse routing tables for every baseline topology
    (the damaged variants above all remain connected)."""
    g = _graph(name, which)
    rt = build_routing(g, engine="sparse")
    pat = make_pattern("uniform", rt, p=2, seed=1, max_flows=4000)
    for mode in ("ecmp", "ugal_pf"):
        vec = build_flow_paths(rt, pat, mode, k_candidates=4, seed=9)
        ref = build_flow_paths_reference(rt, pat, mode, k_candidates=4,
                                         seed=9)
        for f in FIELDS:
            assert np.array_equal(getattr(vec, f), getattr(ref, f)), \
                (name, mode, f)


# ---------------------------------------------------------------------------
# vectorized Graph construction
# ---------------------------------------------------------------------------

def test_csr_view_and_vectorized_construction():
    g = TOPOS["jf"]()
    indptr, indices = g.csr
    assert indptr.dtype == np.int64 and indices.dtype == np.int32
    assert indptr[0] == 0 and indptr[-1] == len(indices) == 2 * g.num_edges
    for u in (0, 5, g.n - 1):
        assert np.array_equal(indices[indptr[u]:indptr[u + 1]],
                              g.neighbors[u])
    # edge_list: u < v, lexicographic, matches the per-edge reference loop
    ref = np.array([(u, int(v)) for u in range(g.n)
                    for v in g.neighbors[u] if u < v], dtype=np.int32)
    assert np.array_equal(g.edge_list, ref)
    # adjacency matches neighbor lists
    adj = g.adjacency
    assert adj.sum() == 2 * g.num_edges
    assert np.array_equal(np.flatnonzero(adj[3]), g.neighbors[3])
    g.validate()


def test_subgraph_without_edges_vectorized():
    g = TOPOS["sf11"]()
    removed = g.edge_list[::3][:10]
    sub = g.subgraph_without_edges(removed)
    sub.validate()
    assert sub.num_edges == g.num_edges - len(removed)
    for u, v in removed:
        assert not sub.has_edge(int(u), int(v))
    # untouched edges survive with sorted neighbor lists
    kept = {tuple(e) for e in map(tuple, g.edge_list)} \
        - {tuple(e) for e in map(tuple, removed)}
    assert kept == {tuple(e) for e in map(tuple, sub.edge_list)}
    # removing nothing is an identity on the adjacency structure
    same = g.subgraph_without_edges(np.zeros((0, 2), dtype=np.int32))
    assert all(np.array_equal(a, b)
               for a, b in zip(same.neighbors, g.neighbors))


# ---------------------------------------------------------------------------
# saturation truncation-error report
# ---------------------------------------------------------------------------

def test_saturation_reports_truncation_error():
    pf = build_polarfly(7)
    rt = build_routing(pf.graph, pf)
    pat = make_pattern("random_perm", rt, p=4, seed=0)
    fp = build_flow_paths(rt, pat, "ugal_pf", k_candidates=6, seed=0)
    sat = saturation_throughput(fp, tol=0.02, iters=250)
    assert isinstance(sat, float) and 0.0 <= sat <= 1.0
    # truncated adaptive solve is noisy
    assert truncation_error(fp, sat, 250) > 0.0
    # oblivious splits are load-independent: exactly zero estimated error
    fp_min = build_flow_paths(rt, make_pattern("uniform", rt, p=4), "min")
    assert truncation_error(fp_min,
                            saturation_throughput(fp_min, tol=0.02)) == 0.0
    # the scalar engine's answer has an estimate too
    sat_sc = saturation_throughput(fp, tol=0.05, iters=60, engine="scalar")
    assert truncation_error(fp, sat_sc, 60) > 0.0


def test_truncation_gap_shrinks_with_iters():
    """At a fixed sub-saturation load the last-vs-averaged load gap decays
    ~O(1/iters) -- the signal callers use to size `fw_iters`."""
    pf = build_polarfly(7)
    rt = build_routing(pf.graph, pf)
    pat = make_pattern("random_perm", rt, p=4, seed=0)
    fp = build_flow_paths(rt, pat, "ugal_pf", k_candidates=6, seed=0)
    eidx, loads_rep, valid, is_min, first_edge, demand, _ = fp.device_arrays()

    def gap(iters):
        return float(fluid_mod._truncation_gap(
            eidx, loads_rep[1:], loads_rep[0], valid, is_min, first_edge,
            demand, fp.num_links, fp.mode, 0.3, iters))

    g50, g4000 = gap(50), gap(4000)
    assert g4000 < 0.25 * g50


# ---------------------------------------------------------------------------
# scale tier (excluded from tier-1 via the `large` marker)
# ---------------------------------------------------------------------------

@pytest.mark.large
@pytest.mark.slow  # belt and braces: a command-line -m replaces the
# addopts "not large" default, so "-m 'not slow'" must still exclude these
def test_scale_tier_ps9x61_sparse():
    """PS(9, 61): 5551 routers at radix 40 -- the first scale-tier point.
    Streams the diameter through the sparse engine and checks the memory
    envelope the benchmark relies on."""
    g = tp.build_polarstar(9, 61)
    assert g.n == 5551
    e_dir = int(g.degrees.sum())
    block = bfs_block_size(g.n, e_dir)
    assert bfs_peak_bytes(g.n, e_dir, block) < 2 * 2 ** 30
    diam, aspl = diameter_and_aspl(g)  # auto -> sparse streaming
    assert diam == 3
    assert 2.0 < aspl < 3.0
    # spot-check one source block against the dense reference on a column
    srcs, db, nh = next(iter(distance_blocks(g, block=4, next_hop=True)))
    from repro.core.routing import bfs_distances
    assert np.array_equal(db[2], bfs_distances(g, int(srcs[2])))
    assert (nh[np.arange(len(srcs)), srcs] == srcs).all()


@pytest.mark.large
@pytest.mark.slow
def test_scale_tier_bisection_pf79():
    g = build_polarfly(79).graph
    assert g.n == 6321
    frac = bisection_fraction(g)
    assert frac > 0.40  # paper Fig. 12: PolarFly stays near-optimal
