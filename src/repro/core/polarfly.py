"""PolarFly: the Erdos-Renyi polarity graph ER_q (paper §IV).

Construction (paper §IV-C/§IV-E): vertices are the left-normalized nonzero
vectors of F_q^3 (= points of PG(2, q)); (v, w) is an edge iff v . w == 0 in
GF(q).  Vertices with v . v == 0 are *quadrics* (W); vertices adjacent to a
quadric form V1; the rest form V2.

N = q^2 + q + 1, degree = q + 1 (quadrics have q neighbors + a conceptual
self-loop), diameter 2, asymptotically Moore optimal.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass, field
from typing import Dict, Tuple

import numpy as np

from ..obs.record import get_recorder
from .gf import GF, is_prime_power
from .graph import Graph

__all__ = ["PolarFly", "build_polarfly", "moore_bound", "moore_efficiency"]


def moore_bound(k: int, d: int = 2) -> int:
    """Moore bound on vertices for max degree k, diameter d (paper eq. (1))."""
    n = 1
    term = k
    for _ in range(d):
        n += term
        term *= (k - 1)
    return n


def moore_efficiency(n: int, k: int, d: int = 2) -> float:
    return n / moore_bound(k, d)


def _enumerate_projective_points(q: int) -> np.ndarray:
    """All left-normalized nonzero vectors of F_q^3, shape [q^2+q+1, 3].

    Order: [0,0,1], [0,1,z], [1,y,z] (lexicographic within each class).
    """
    pts = [(0, 0, 1)]
    for z in range(q):
        pts.append((0, 1, z))
    for y in range(q):
        for z in range(q):
            pts.append((1, y, z))
    return np.array(pts, dtype=np.int32)


def _vertex_ids(vt: np.ndarray, q: int, points: np.ndarray) -> np.ndarray:
    """Ids of left-normalized `points` [..., 3] among the vertices `vt`
    (positional code (x·q + y)·q + z; -1 for the zero vector)."""
    lut = -np.ones(q ** 3, dtype=np.int32)
    lut[(vt[:, 0] * q + vt[:, 1]) * q + vt[:, 2]] = np.arange(
        len(vt), dtype=np.int32)
    return lut[(points[..., 0] * q + points[..., 1]) * q + points[..., 2]]


@dataclass
class PolarFly:
    """ER_q polarity graph with PolarFly vertex taxonomy."""

    q: int
    gf: GF = field(repr=False)
    graph: Graph = field(repr=False)
    vertices: np.ndarray = field(repr=False)  # [N, 3] left-normalized vectors
    quadric_mask: np.ndarray = field(repr=False)  # [N] bool  (W)
    v1_mask: np.ndarray = field(repr=False)  # [N] bool
    v2_mask: np.ndarray = field(repr=False)  # [N] bool
    index: Dict[Tuple[int, int, int], int] = field(repr=False)

    @property
    def n(self) -> int:
        return self.graph.n

    @property
    def degree(self) -> int:
        """Network radix k = q + 1."""
        return self.q + 1

    @functools.cached_property
    def quadrics(self) -> np.ndarray:
        return np.where(self.quadric_mask)[0].astype(np.int32)

    @functools.cached_property
    def v1(self) -> np.ndarray:
        return np.where(self.v1_mask)[0].astype(np.int32)

    @functools.cached_property
    def v2(self) -> np.ndarray:
        return np.where(self.v2_mask)[0].astype(np.int32)

    def vertex_id(self, vec) -> int:
        v = self.gf.normalize3(np.asarray(vec, dtype=np.int32))
        return self.index[tuple(int(x) for x in v)]

    # -- paper §IV-D: minimal-route intermediate vertex ----------------------
    def intermediate(self, s: int, d: int) -> int:
        """Unique mid vertex of the 2-hop s->d path via GF cross product."""
        c = self.gf.cross3(self.vertices[s], self.vertices[d])
        c = self.gf.normalize3(c)
        return self.index[tuple(int(x) for x in c)]

    def intermediates_all_pairs(self) -> np.ndarray:
        """[N, N] int32 table of 2-hop intermediate vertices.

        Entry [s, d] is the unique intermediate vertex of the minimal 2-hop
        path (meaningful when s, d are distinct and non-adjacent; for adjacent
        pairs it is the common neighbor completing the unique triangle /
         2-hop alternative, and for s == d it degenerates).
        """
        vt = self.vertices
        c = self.gf.cross3(vt[:, None, :], vt[None, :, :])  # [N, N, 3]
        return _vertex_ids(vt, self.q, self.gf.normalize3(c))


def build_polarfly(q: int) -> PolarFly:
    """Construct ER_q for any prime power q.

    Each vertex v's neighbours are the q + 1 points of its polar line v^⊥
    (a quadric's own point dropped): two independent vectors a, b of v^⊥
    come from cross products of v with the standard basis, and the line's
    points are normalize(b + t·a) for t in GF(q), plus a.  O(N·q) table
    lookups, for every prime power.

    Spans (``repro.obs``): ``polarfly.points`` (the field and the
    projective points), ``polarfly.adjacency`` (the polar lines and the
    sorted neighbour lists), ``polarfly.classify`` (quadrics, V1, V2, the
    graph and the vertex index)."""
    if not is_prime_power(q):
        raise ValueError(f"q={q} must be a prime power")
    rec = get_recorder()
    with rec.span("polarfly.points", q=q):
        gf = GF(q)
        vt = _enumerate_projective_points(q)  # [N, 3]
    n = vt.shape[0]
    assert n == q * q + q + 1

    with rec.span("polarfly.adjacency", n=n):
        quadric = gf.dot3(vt, vt) == 0
        # v x e_j spans v^⊥ over the two basis vectors e_j off v's leading
        # coordinate (v has a 1 there, so the pair is independent).
        crosses = gf.cross3(vt[:, None, :], np.eye(3, dtype=np.int32))
        lead = np.argmax(vt != 0, axis=1)
        off_lead = np.array([[1, 2], [0, 2], [0, 1]])[lead]  # [N, 2]
        rows = np.arange(n)[:, None]
        a, b = crosses[rows, off_lead].transpose(1, 0, 2)[:, :, None, :]
        t = np.arange(q, dtype=np.int32)[None, :, None]
        line = gf.add(b, gf.mul(t, a))  # [N, q, 3]
        line = np.concatenate([line, a], axis=1)  # [N, q + 1, 3]
        ids = _vertex_ids(vt, q, gf.normalize3(line))
        ids[ids == rows] = n  # a quadric lies on its own line
        ids.sort(axis=1)
        degree = (q + 1 - quadric).tolist()
        neighbors = [row[:d] for row, d in zip(ids, degree)]

    with rec.span("polarfly.classify"):
        v1 = np.zeros(n, dtype=bool)
        for w in np.where(quadric)[0]:
            v1[neighbors[w]] = True
        v1 &= ~quadric
        v2 = ~(quadric | v1)

        graph = Graph(
            f"PF({q})", n, neighbors,
            params={"q": q, "radix": q + 1},
            labels={"quadric": quadric, "v1": v1, "v2": v2, "vectors": vt},
        )
        index = {tuple(v): i for i, v in enumerate(vt.tolist())}
    return PolarFly(q=q, gf=gf, graph=graph, vertices=vt,
                    quadric_mask=quadric, v1_mask=v1, v2_mask=v2, index=index)
