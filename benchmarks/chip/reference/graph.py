"""Plain reference for the graph, routing and path layers.

Builds PolarFly ER_q from its definition, routes it by breadth-first
search, and checks the program's candidate paths against minimal paths
taken with the lowest-id tie break.  Imports nothing of the program.

Conventions the program documents and the comparison relies on:

* router ids: the left-normalized points of PG(2, q) in the order
  [0,0,1], [0,1,z] (z = 0..q-1), [1,y,z] (y, z = 0..q-1);
  (u, v) is a link iff u . v == 0 (mod q), u != v;
* directed link ids: CSR order, id = offset[u] + rank of v among the
  sorted neighbours of u;
* minimal path: from u toward d, the next hop is the smallest-id neighbour
  one step closer to d;
* a Valiant candidate is the minimal path s -> r followed by the minimal
  path r -> d, for an intermediate r other than s and d.
"""

from __future__ import annotations

import numpy as np


class RefGraph:
    """An undirected graph in CSR form with sorted neighbour rows."""

    def __init__(self, n: int, indptr: np.ndarray, indices: np.ndarray):
        self.n = n
        self.indptr = indptr.astype(np.int64)
        self.indices = indices.astype(np.int64)
        self.num_links = len(indices)
        deg = np.diff(self.indptr)
        self.nb = np.full((n, int(deg.max())), -1, dtype=np.int64)
        rows = np.repeat(np.arange(n), deg)
        cols = np.arange(self.num_links) - np.repeat(self.indptr[:-1], deg)
        self.nb[rows, cols] = self.indices
        self._keys = rows * n + self.indices  # sorted: CSR is row-major
        self._src = rows

    def link_ids(self, u: np.ndarray, v: np.ndarray) -> np.ndarray:
        """Directed link id of each (u, v); -1 where there is no link."""
        key = np.asarray(u, np.int64) * self.n + np.asarray(v, np.int64)
        pos = np.minimum(np.searchsorted(self._keys, key), self.num_links - 1)
        return np.where(self._keys[pos] == key, pos, -1)

    def link_ends(self, e: np.ndarray):
        """(source, target) of directed link ids."""
        e = np.asarray(e, np.int64)
        return self._src[e], self.indices[e]


def polarfly(q: int) -> RefGraph:
    """ER_q for a prime q, from its definition."""
    if q < 2 or any(q % k == 0 for k in range(2, int(q ** 0.5) + 1)):
        raise ValueError(f"the reference builds ER_q for prime q only, "
                         f"not {q}")
    pts = [(0, 0, 1)] + [(0, 1, z) for z in range(q)] \
        + [(1, y, z) for y in range(q) for z in range(q)]
    p = np.array(pts, dtype=np.int64)
    n = len(p)
    rows, cols = [], []
    for lo in range(0, n, 512):
        orth = (p[lo:lo + 512] @ p.T) % q == 0
        r, c = np.nonzero(orth)
        keep = (r + lo) != c
        rows.append(r[keep] + lo)
        cols.append(c[keep])
    rows, cols = np.concatenate(rows), np.concatenate(cols)
    order = np.lexsort((cols, rows))
    indptr = np.searchsorted(rows[order], np.arange(n + 1))
    return RefGraph(n, indptr, cols[order])


def distances(g: RefGraph, dests: np.ndarray) -> np.ndarray:
    """[len(dests), n] int16 hop distances to each destination, by a
    level-synchronous breadth-first search (-1 where unreachable)."""
    import scipy.sparse as sp

    a = sp.csr_matrix((np.ones(g.num_links, np.float32), g.indices,
                       g.indptr), shape=(g.n, g.n))
    dests = np.asarray(dests, np.int64)
    out = np.full((len(dests), g.n), -1, dtype=np.int16)
    for lo in range(0, len(dests), 1024):
        d = dests[lo:lo + 1024]
        b = len(d)
        seen = np.zeros((g.n, b), dtype=bool)
        seen[d, np.arange(b)] = True
        frontier = seen.astype(np.float32)
        dist = np.full((g.n, b), -1, dtype=np.int16)
        dist[d, np.arange(b)] = 0
        level = 0
        while frontier.any():
            level += 1
            reach = (a @ frontier) > 0
            new = reach & ~seen
            dist[new] = level
            seen |= new
            frontier = new.astype(np.float32)
        out[lo:lo + b] = dist.T
    return out


def distance_table(g: RefGraph, dests=None) -> np.ndarray:
    """[n, n] table whose row d holds every router's distance to d, filled
    for the rows `dests` (all rows when None; -1 elsewhere)."""
    rows = np.arange(g.n) if dests is None else np.unique(dests)
    table = np.full((g.n, g.n), -1, dtype=np.int16)
    table[rows] = distances(g, rows)
    return table


def min_paths(g: RefGraph, table: np.ndarray, src: np.ndarray,
              dst: np.ndarray, max_hops: int) -> np.ndarray:
    """[R, max_hops] directed link ids (-1 padded) of the minimal path of
    each (src, dst) pair; row d of `table` holds the distances to d."""
    r = len(src)
    out = np.full((r, max_hops), -1, dtype=np.int64)
    u = np.asarray(src, np.int64).copy()
    dst = np.asarray(dst, np.int64)
    rows = table[dst]  # [R, n]
    idx = np.arange(r)
    for h in range(max_hops):
        live = u != dst
        if not live.any():
            break
        cand = g.nb[u]  # [R, deg]
        du = rows[idx, u]
        ok = (cand >= 0) & (rows[idx[:, None], np.maximum(cand, 0)]
                            == (du - 1)[:, None])
        nxt = cand[idx, np.argmax(ok, axis=1)]
        if (live & ~ok.any(axis=1)).any():
            raise ValueError("no route to a destination")
        e = g.link_ids(u, nxt)
        out[live, h] = e[live]
        u = np.where(live, nxt, u)
    if (u != dst).any():
        raise ValueError(f"a minimal path is longer than {max_hops} hops")
    return out


def pad_to(rows: np.ndarray, width: int) -> np.ndarray:
    out = np.full((rows.shape[0], width), -1, dtype=np.int64)
    out[:, :rows.shape[1]] = rows[:, :width]
    return out


def check_paths(g: RefGraph, src, dst, edges, hops, valid, mode: str,
                flows: np.ndarray, dist_all: np.ndarray = None):
    """Compare the program's candidate paths of the flows `flows` with the
    reference's.  Returns (bad, ref_edges): `bad` counts candidates
    (flow, slot) that differ from the reference in links, hop count or
    validity; `ref_edges` [len(flows), K, L] are the reference's own
    candidates, built from the intermediate each Valiant slot names
    (-1 padded; a slot whose path is no Valiant path of any intermediate
    keeps the reference minimal path there, and counts as bad).

    `mode` is "min" (slot 0 only) or "ugal" (slot 0 minimal, slots 1..K-1
    Valiant).  `dist_all`, when given, is the [n, n] distance table."""
    src = np.asarray(src, np.int64)[flows]
    dst = np.asarray(dst, np.int64)[flows]
    edges = np.asarray(edges, np.int64)[flows]
    hops = np.asarray(hops)[flows]
    valid = np.asarray(valid)[flows]
    f, k, lmax = edges.shape
    if dist_all is None:
        dist_all = distance_table(g, dst if mode == "min" else None)
    ref = np.full((f, k, lmax), -1, dtype=np.int64)
    m = min_paths(g, dist_all, src, dst, lmax)
    ref[:, 0] = m
    bad = np.zeros((f, k), dtype=bool)
    bad[:, 0] = ((edges[:, 0] != m).any(axis=1) | ~valid[:, 0]
                 | (hops[:, 0] != (m >= 0).sum(axis=1)))
    if mode == "min":
        return int(bad.sum()) + int(k != 1) * f, ref
    # Valiant slots: find an intermediate r = node j of the path such that
    # the path is minimal(s, r) + minimal(r, d)
    e = edges[:, 1:].reshape(-1, lmax)
    s_rep = np.repeat(src, k - 1)
    d_rep = np.repeat(dst, k - 1)
    real = e >= 0
    found = np.zeros(len(e), dtype=bool)
    best = np.full((len(e), lmax), -1, dtype=np.int64)
    for j in range(1, lmax):
        has = real[:, j - 1]
        r = np.where(has, g.link_ends(np.maximum(e[:, j - 1], 0))[1], s_rep)
        ok_r = has & (r != s_rep) & (r != d_rep)
        r_safe = np.where(ok_r, r, np.where(s_rep != 0, 0, 1))
        d_safe = np.where(ok_r, d_rep, np.where(r_safe != d_rep, d_rep,
                                                 s_rep))
        first = min_paths(g, dist_all, s_rep, r_safe, lmax)
        second = min_paths(g, dist_all, r_safe, d_safe, lmax)
        h1 = (first >= 0).sum(axis=1)
        cand = np.full((len(e), 2 * lmax), -1, dtype=np.int64)
        cand[:, :lmax] = first
        cols = h1[:, None] + np.arange(lmax)[None, :]
        np.put_along_axis(cand, cols, second, axis=1)
        cand = cand[:, :lmax]
        match = ok_r & (cand == e).all(axis=1) & ((cand >= 0).sum(axis=1)
                                                 <= lmax)
        best[match & ~found] = cand[match & ~found]
        found |= match
    vh = hops[:, 1:].reshape(-1)
    vv = valid[:, 1:].reshape(-1)
    vbad = ~found | ~vv | (vh != real.sum(axis=1))
    bad[:, 1:] = vbad.reshape(f, k - 1)
    best[~found] = np.repeat(m, k - 1, axis=0)[~found]
    ref[:, 1:] = best.reshape(f, k - 1, lmax)
    return int(bad.sum()), ref
