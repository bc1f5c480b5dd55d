"""Cycle-driven flit-level packet engine: tail latency under transients.

The fluid solver answers steady-state questions; this engine answers the
operational ones -- p50/p99/p999 packet latency under bursts, adaptive
routing transients, and mid-run link failures (the quantities the Slim
Fly deployment study measures on real hardware; see PAPERS.md).

Model (one spec, two engines)
-----------------------------
Wormhole-ish store-and-forward at packet granularity with flit-level
timing: every directed link has a FIFO output queue of `capacity`
packets; the head packet serializes for `size` cycles (one flit per
cycle) before it may advance; advancing requires a free slot in the next
link's queue (credit-based backpressure, credits returned with a
one-cycle delay: a slot freed this cycle is usable next cycle).  Each
cycle runs the same five phases in both engines:

1. serialization countdown: every non-empty link's head decrements its
   remaining service (floor 0); heads at 0 are *ready*.
2. in-flight intents: each ready head names its next link from its
   chosen candidate path (the stepping-core-built `FlowPaths` arrays),
   or exits if the path is exhausted (delivery always succeeds).
3. injection intents: per source router, the oldest pending packet
   (arrival ordering) chooses its candidate *now* -- oblivious modes use
   a pre-drawn index, UGAL picks ``argmin_c hops[c] + occupancy(first
   link of c)`` over valid candidates (UGAL_PF additionally keeps the
   minimal candidate unless the min path's first queue is at least 2/3
   full, the paper's adaptation gate) -- and bids for its first link.
4. arbitration per target link: `capacity - occupancy` slots (occupancy
   at cycle start) go to in-flight candidates in upstream-link-id order,
   then to the (unique) injection bid if a slot remains.  Losers stall
   and retry; winners append in that order.
5. head changes (departure or arrival-to-empty) reset the new head's
   serialization clock to `size`.

All quantities are integers and every tie is broken deterministically,
so the scalar reference and the batched engine agree **bit-identically**
on the delivered-packet latency multiset (tests/test_packet_engine.py
asserts it per graph x mode x damage combination).

Engines:

* `simulate_packets_reference` -- per-flit/per-queue Python event loop,
  explicit list queues, conservation invariants (no packet lost or
  duplicated, queues bounded by `capacity`, serialization clocks in
  range) asserted every cycle.  The executable spec.
* `simulate_packets` -- the scale engine: per-link queues as one dense
  ``[E + 1, Q]`` id matrix (row E is the arbitration dump row), a
  `lax.scan` over cycles, sort-based arbitration (stable argsort by
  target, then ranks within each target's run from a running max of the
  run starts and per-link counts from each run's last rank -- no search
  and no ``.at[].add()`` scatter on the cycle path), gather-only routing
  lookups, no host syncs inside jit, and no ``[n, n]`` allocation
  anywhere.  `simulate_packets_batch` vmaps the same scan over a stack
  of same-shape workloads (e.g. seed replicas) in one dispatch.

Scenarios (`make_workload` / `build_failure_workload`): steady uniform /
tornado / any `TrafficPattern` load, on-off bursts (`BurstSchedule`,
mean-preserving by default), and a mid-run link-failure transient --
epoch-0 paths up to `switch_cycle`, re-routed epoch-1 paths (built on
the damaged graph, remapped into the intact edge-id space via the
stepping core's CSR row recovery) afterwards; in-network packets whose
remaining path crosses a failed link are dropped at the switch, pending
packets re-decide on the new tables.

Per-packet routes are *not* rebuilt here: candidates come from
`build_flow_paths` (which itself rides `repro.core.stepping`), so the
packet engine consumes exactly the `RoutingTables` / `BlockedRouting`
next-hop machinery the fluid solver uses -- one path-construction stack,
two time resolutions.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core.stepping import edge_sources
from ..obs.record import get_recorder
from ..parallel.blockwise import peak_bytes
from .paths import DirectedEdges, FlowPaths, build_directed_edges, \
    build_flow_paths
from .traffic import TrafficPattern

__all__ = ["BurstSchedule", "PacketWorkload", "PacketResult",
           "make_workload", "build_failure_workload", "remap_edge_space",
           "simulate_packets", "simulate_packets_reference",
           "simulate_packets_batch", "packet_peak_bytes", "tail_percentiles",
           "occupancy_histogram", "record_occupancy"]

# Paper §VIII-A buffering: 128-flit buffers, 4-flit packets -> 32-packet
# queues; the same constants the fluid solver's M/D/1 delay model uses
# (`fluid._BUF_PACKETS`).
DEFAULT_PACKET_FLITS = 4
DEFAULT_QUEUE_PACKETS = 32

# candidate-cost infinity for invalid slots (int32-safe)
_BIG = np.int32(2 ** 30)


def _gate_occ(capacity: int) -> int:
    """UGAL_PF adaptation gate in packets: adapt away from the minimal
    path only once its first queue is >= 2/3 full (paper §VII-C)."""
    return -(-2 * capacity // 3)


# --------------------------------------------------------------------------
# workload construction (host side, shared verbatim by both engines)
# --------------------------------------------------------------------------

@dataclass(frozen=True)
class BurstSchedule:
    """On-off injection modulation: each flow injects only during the
    `on`-cycle window of every `on + off` period (per-flow phase offsets
    are drawn by `make_workload`, desynchronizing flows); `scale`
    multiplies the on-window rate -- the default 0.0 means
    mean-preserving, ``(on + off) / on``."""
    on: int
    off: int
    scale: float = 0.0

    @property
    def period(self) -> int:
        return self.on + self.off

    def rate_scale(self) -> float:
        return self.scale if self.scale > 0 else self.period / self.on


@dataclass
class PacketWorkload:
    """Everything both engines consume, fully materialized host-side.

    Path arrays are epoch-stacked ([0] before `switch_cycle`, [1] after;
    without a failure scenario both epochs alias the same tables): `eidx`
    holds each candidate's directed-edge sequence padded with `num_links`
    (the exit marker), one column wider than the hop budget so the
    per-cycle next-edge gather never branches.  Packets are sorted by
    (source router, arrival cycle) and identified by their index;
    `src_off` gives each source's contiguous packet segment, which is
    what makes per-source FIFO injection a pointer per source.
    """
    eidx: np.ndarray       # [2, F, K, L + 1] int32, pads/exit -> num_links
    hops: np.ndarray       # [2, F, K] int32
    n_valid: np.ndarray    # [2, F] int32 (valid candidates are a prefix)
    pkt_flow: np.ndarray   # [P] int32
    pkt_t: np.ndarray      # [P] int32 arrival cycles (nondecreasing per src)
    pkt_cand: np.ndarray   # [2, P] int32 pre-drawn oblivious candidate
    src_off: np.ndarray    # [n + 1] int64 per-source packet segments
    num_links: int
    num_nodes: int
    size: int              # flits per packet == serialization cycles per hop
    capacity: int          # per-link queue capacity, packets
    cycles: int
    mode: str
    switch_cycle: int      # == cycles when there is no failure epoch
    fail_hop: np.ndarray   # [F, K] int32 last failed hop on epoch-0 paths
    #   (L + 1 for clean paths; a packet at hop h is dropped iff
    #    h <= fail_hop < hops -- some failed link is still ahead of or
    #    under it)
    pattern_name: str = ""

    @property
    def num_packets(self) -> int:
        return len(self.pkt_flow)

    @property
    def num_flows(self) -> int:
        return self.eidx.shape[1]

    @property
    def adaptive(self) -> bool:
        return self.mode in ("ugal", "ugal_pf")

    @property
    def gated(self) -> bool:
        return self.mode == "ugal_pf"


def _epoch_tables(fp: FlowPaths, edges: np.ndarray, hops: np.ndarray,
                  valid: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """One epoch's (eidx [F, K, L + 1], n_valid [F]) from candidate arrays
    in `fp`'s edge-id space; asserts the prefix-validity every mode the
    engine supports satisfies (oblivious draws index the prefix)."""
    f, k, l = edges.shape
    n_valid = valid.sum(axis=1).astype(np.int32)
    if not (n_valid >= 1).all():
        raise ValueError("every flow needs at least one valid candidate")
    if not (valid == (np.arange(k) < n_valid[:, None])).all():
        raise ValueError("packet engine requires prefix-valid candidates")
    eidx = np.full((f, k, l + 1), fp.num_links, dtype=np.int32)
    real = edges >= 0
    eidx[:, :, :l] = np.where(real, edges, fp.num_links)
    # exit marker position == hops is automatic: pads already map to E
    return eidx, n_valid


def remap_edge_space(edges: np.ndarray, de_from: DirectedEdges,
                     de_to: DirectedEdges) -> np.ndarray:
    """Remap -1-padded directed-edge ids from one graph's CSR id space to
    another's (damaged subgraph -> intact parent).  Recovers each edge's
    (source, target) pair via the stepping core's CSR row recovery, then
    looks the pair up in the target space.  Raises if a real edge has no
    image (the damaged graph must be a subgraph)."""
    real = edges >= 0
    safe = np.where(real, edges, 0)
    u = edge_sources(de_from.offsets, safe)
    v = de_from.targets[safe]
    mapped = de_to.edge_ids(u, v)
    if not (mapped[real] >= 0).all():
        raise ValueError("edge remap misses: not a subgraph of the target")
    return np.where(real, mapped, np.int32(-1)).astype(np.int32)


def _injection_times(demand: np.ndarray, offered: float, size: int,
                     cycles: int, burst: Optional[BurstSchedule],
                     phase: np.ndarray, bphase: np.ndarray,
                     chunk: int = 2048) -> Tuple[np.ndarray, np.ndarray]:
    """Arrival times per flow from a credit accumulator: flow f earns
    ``offered * demand[f] / size`` packets per cycle (scaled inside burst
    on-windows), seeded with a fractional phase in [0, 1); a packet
    arrives whenever the accumulator crosses an integer.  Returns
    (pkt_flow, pkt_t) unsorted; chunked over flows so the [F, T] credit
    matrix never materializes whole."""
    f = len(demand)
    rate = offered * demand.astype(np.float64) / float(size)
    t = np.arange(cycles, dtype=np.int64)
    flows: List[np.ndarray] = []
    times: List[np.ndarray] = []
    for lo in range(0, f, chunk):
        hi = min(f, lo + chunk)
        r = np.broadcast_to(rate[lo:hi, None], (hi - lo, cycles))
        if burst is not None:
            active = ((t[None, :] + bphase[lo:hi, None]) % burst.period
                      ) < burst.on
            r = r * (burst.rate_scale() * active)
        cum = phase[lo:hi, None] + np.cumsum(r, axis=1)
        cnt = np.floor(cum).astype(np.int64)
        prev = np.concatenate(
            [np.zeros((hi - lo, 1), dtype=np.int64), cnt[:, :-1]], axis=1)
        k_new = cnt - prev  # packets arriving at cycle t
        fi, ti = np.nonzero(k_new)
        rep = k_new[fi, ti]
        flows.append(np.repeat(fi + lo, rep).astype(np.int32))
        times.append(np.repeat(ti, rep).astype(np.int32))
    return (np.concatenate(flows) if flows else np.zeros(0, np.int32),
            np.concatenate(times) if times else np.zeros(0, np.int32))


def make_workload(fp: FlowPaths, offered: float, cycles: int, *,
                  size: int = DEFAULT_PACKET_FLITS,
                  capacity: int = DEFAULT_QUEUE_PACKETS,
                  burst: Optional[BurstSchedule] = None,
                  after: Optional[Tuple[np.ndarray, np.ndarray,
                                        np.ndarray]] = None,
                  switch_cycle: Optional[int] = None,
                  failed_edges: Optional[np.ndarray] = None,
                  num_nodes: Optional[int] = None,
                  flow_sample: Optional[int] = None,
                  max_packets: int = 400_000, seed: int = 0,
                  rng: Optional[np.random.Generator] = None
                  ) -> PacketWorkload:
    """Materialize a packet workload from flow candidates.

    `offered` scales the pattern's per-flow demand (flits/cycle at unit
    load) into packet arrival rates.  `burst` switches steady injection
    to on-off windows.  `after` = (edges, hops, valid) supplies epoch-1
    re-routed candidates **already remapped into fp's edge-id space**
    (see `build_failure_workload` for the assembled scenario) active
    from `switch_cycle` on, with `failed_edges` naming the dead directed
    links (epoch-0 packets still due to cross one are dropped at the
    switch).  `flow_sample` draws that many flows up front (the
    sampled-flow scale tier).  All randomness -- flow sampling, phases,
    oblivious candidate draws -- comes from the single `rng`
    (`np.random.default_rng(seed)` when not given), in a fixed order, so
    equal seeds give identical workloads and therefore identical tail
    metrics from either engine.  Runs in a ``packet.workload`` span
    (``repro.obs``).
    """
    with get_recorder().span("packet.workload", cycles=cycles):
        if rng is None:
            rng = np.random.default_rng(seed)
        pat = fp.pattern
        nn = int(num_nodes if num_nodes is not None
                 else max(int(pat.src.max()), int(pat.dst.max())) + 1)
        sel = np.arange(fp.pattern.num_flows)
        if flow_sample is not None and flow_sample < len(sel):
            sel = np.sort(rng.choice(len(sel), size=flow_sample,
                                     replace=False))
        edges0, hops0, valid0 = (fp.edges[sel], fp.hops[sel], fp.valid[sel])
        src, demand = pat.src[sel], pat.demand[sel]
        eidx0, nv0 = _epoch_tables(fp, edges0, hops0, valid0)
        if after is not None:
            e1, h1, v1 = after
            eidx1, nv1 = _epoch_tables(fp, e1[sel], h1[sel], v1[sel])
            hops1 = h1[sel]
            if switch_cycle is None:
                raise ValueError("failure epoch needs switch_cycle")
        else:
            eidx1, nv1, hops1 = eidx0, nv0, hops0
            switch_cycle = cycles
        # epochs may disagree on max path length (re-routes around failures
        # run longer): pad both to the wider hop budget with the exit marker
        lmax = max(eidx0.shape[2], eidx1.shape[2])
        pad_l = lambda a: np.concatenate(  # noqa: E731
            [a, np.full(a.shape[:2] + (lmax - a.shape[2],), fp.num_links,
                        dtype=np.int32)], axis=2)
        eidx = np.stack([pad_l(eidx0), pad_l(eidx1)])
        hops2 = np.stack([hops0.astype(np.int32), hops1.astype(np.int32)])
        n_valid = np.stack([nv0, nv1])

        # last failed hop per epoch-0 candidate (L + 1 when the path is
        # clean); the drop test `hop <= fail_hop` must see the *last* failed
        # link, or a packet past one failure but short of a second survives
        l1 = eidx.shape[3]
        if failed_edges is not None and len(failed_edges):
            fmask = np.zeros(fp.num_links + 1, dtype=bool)
            fmask[np.asarray(failed_edges, dtype=np.int64)] = True
            onpath = fmask[eidx0]  # [F, K, L0 + 1] (pre-pad width)
            anyf = onpath.any(axis=2)
            last = onpath.shape[2] - 1 - onpath[:, :, ::-1].argmax(axis=2)
            fail_hop = np.where(anyf, last, l1).astype(np.int32)
        else:
            fail_hop = np.full(hops0.shape, l1, dtype=np.int32)

        phase = rng.random(len(sel))
        bphase = (rng.integers(burst.period, size=len(sel))
                  if burst is not None else np.zeros(len(sel), np.int64))
        pkt_flow, pkt_t = _injection_times(demand, offered, size, cycles,
                                           burst, phase, bphase)
        if len(pkt_flow) > max_packets:
            raise ValueError(
                f"{len(pkt_flow)} packets exceed max_packets={max_packets}; "
                "lower offered/cycles or pass flow_sample")
        # id order = (source router, arrival cycle, flow): per-source FIFO
        order = np.lexsort((pkt_flow, pkt_t, src[pkt_flow]))
        pkt_flow, pkt_t = pkt_flow[order], pkt_t[order]
        src_off = np.searchsorted(src[pkt_flow], np.arange(nn + 1),
                                  side="left").astype(np.int64)
        u = rng.random(len(pkt_flow))
        pkt_cand = np.stack([
            np.minimum((u * n_valid[ep, pkt_flow]).astype(np.int32),
                       n_valid[ep, pkt_flow] - 1)
            for ep in (0, 1)])
        return PacketWorkload(
            eidx=eidx, hops=hops2, n_valid=n_valid, pkt_flow=pkt_flow,
            pkt_t=pkt_t, pkt_cand=pkt_cand, src_off=src_off,
            num_links=fp.num_links, num_nodes=nn, size=size, capacity=capacity,
            cycles=cycles, mode=fp.mode, switch_cycle=int(switch_cycle),
            fail_hop=fail_hop, pattern_name=pat.name)


def build_failure_workload(rt, rt_after, pattern: TrafficPattern, mode: str,
                           offered: float, cycles: int, switch_cycle: int,
                           *, k_candidates: int = 8, seed: int = 0,
                           rng: Optional[np.random.Generator] = None,
                           **kw) -> PacketWorkload:
    """Assemble the mid-run link-failure transient: epoch-0 candidates on
    `rt` (intact), epoch-1 candidates on `rt_after` (whose graph must be
    an edge-subgraph of the intact one), remapped into the intact
    directed-edge space; directed links missing from the damaged graph
    become the failure set.  Extra keyword arguments pass through to
    `make_workload`."""
    fp = build_flow_paths(rt, pattern, mode, k_candidates=k_candidates,
                          seed=seed)
    fp2 = build_flow_paths(rt_after, pattern, mode,
                           k_candidates=k_candidates, seed=seed)
    de = build_directed_edges(rt.graph)
    de2 = build_directed_edges(rt_after.graph)
    edges1 = remap_edge_space(fp2.edges, de2, de)
    # failed = intact directed edges with no image in the damaged space
    u = edge_sources(de.offsets, np.arange(de.num))
    failed = np.flatnonzero(de2.edge_ids(u, de.targets) < 0)
    return make_workload(fp, offered, cycles,
                         after=(edges1, fp2.hops, fp2.valid),
                         switch_cycle=switch_cycle, failed_edges=failed,
                         num_nodes=rt.graph.n, seed=seed, rng=rng, **kw)


# --------------------------------------------------------------------------
# results
# --------------------------------------------------------------------------

def tail_percentiles(latencies: np.ndarray,
                     qs: Sequence[float] = (0.5, 0.99, 0.999)
                     ) -> Dict[str, int]:
    """Nearest-rank percentiles of an integer latency sample (exact order
    statistics -- no interpolation, so engine comparisons stay integer).
    Keys are p50/p99/p999-style."""
    lat = np.sort(np.asarray(latencies))
    if not len(lat):
        raise ValueError("no delivered packets to take percentiles of")
    out = {}
    for q in qs:
        idx = max(0, int(np.ceil(q * len(lat))) - 1)
        key = f"p{q * 100:g}".replace(".", "")
        out[key] = int(lat[idx])
    return out


@dataclass
class PacketResult:
    """Per-packet outcomes + time-resolved occupancy from one run."""
    deliver_t: np.ndarray   # [P] int32 (undefined where not delivered)
    delivered: np.ndarray   # [P] bool
    dropped: np.ndarray     # [P] bool (failure-transient casualties)
    inject_t: np.ndarray    # [P] int32 arrival cycles
    occ_sum: np.ndarray     # [T] int32 total queued packets, end of cycle
    occ_max: np.ndarray     # [T] int32 max per-link queue depth
    occ_rec: np.ndarray     # [T, R] int32 tracked links' depths (R may be 0)
    cycles: int
    size: int
    capacity: int
    admitted: int           # packets that entered the network (conservation:
    #   delivered + dropped + occ_sum[-1] == admitted <= P)

    def latencies(self) -> np.ndarray:
        """Sorted int32 latency multiset of delivered packets."""
        lat = (self.deliver_t[self.delivered]
               - self.inject_t[self.delivered]).astype(np.int32)
        return np.sort(lat)

    def histogram(self) -> np.ndarray:
        """Latency histogram (bin = cycle)."""
        lat = self.latencies()
        return np.bincount(lat) if len(lat) else np.zeros(1, np.int64)

    def tails(self) -> Dict[str, int]:
        return tail_percentiles(self.latencies())

    @property
    def num_delivered(self) -> int:
        return int(self.delivered.sum())

    @property
    def num_dropped(self) -> int:
        return int(self.dropped.sum())


def packet_peak_bytes(wl: PacketWorkload) -> int:
    """Estimated resident bytes of the batched engine's scan state: the
    dense queue matrix + per-link scalars, the epoch-stacked candidate
    tables, and the per-packet bookkeeping -- composed from the shared
    blockwise accounting helper, like the routing/path estimators.  No
    term scales as [n, n]."""
    e, p = wl.num_links, wl.num_packets
    f, k, l1 = wl.eidx.shape[1:]
    resident = 4 * ((e + 1) * wl.capacity + 4 * e)  # queues + occ/serve/etc
    resident += 4 * (2 * f * k * (l1 + 1) + 2 * f)  # eidx/hops/n_valid
    return peak_bytes(p, 7 * 4, resident_bytes=resident)


def occupancy_histogram(res: PacketResult,
                        max_depth: Optional[int] = None) -> np.ndarray:
    """Per-cycle max-queue-depth histogram: `hist[d]` = cycles whose
    deepest link queue held exactly `d` packets.  Bins run 0..capacity
    (or `max_depth`), so saturated runs show mass in the top bin."""
    cap = res.capacity if max_depth is None else int(max_depth)
    occ = np.minimum(res.occ_max, cap)
    return np.bincount(occ, minlength=cap + 1)


def record_occupancy(res: PacketResult, name: str = "packet",
                     recorder=None) -> Dict[str, float]:
    """Surface a run's per-cycle occupancy traces as obs metrics.

    Both engines already produce `occ_sum` / `occ_max` per cycle; this
    turns them into a queue-depth histogram, summary gauges, and
    downsampled time series on the (given or global) recorder, and
    returns the summary dict.  Host-side numpy only -- the batched
    engine's scan outputs have already been fetched by the time a
    `PacketResult` exists."""
    rec = recorder if recorder is not None else get_recorder()
    occ_sum = np.asarray(res.occ_sum)
    occ_max = np.asarray(res.occ_max)
    cycles = int(res.cycles)
    summary = {
        "cycles": float(cycles),
        "occ_mean": float(occ_sum.mean()) if cycles else 0.0,
        "occ_peak": float(occ_max.max(initial=0)),
        "occ_p99": float(np.percentile(occ_max, 99)) if cycles else 0.0,
        "saturated_frac": float((occ_max >= res.capacity).mean())
        if cycles else 0.0,
    }
    rec.histogram(f"{name}.queue_depth", np.minimum(occ_max, res.capacity))
    rec.series(f"{name}.occ_sum", occ_sum)
    rec.series(f"{name}.occ_max", occ_max)
    for key, v in summary.items():
        rec.gauge(f"{name}.{key}", v)
    return summary


# --------------------------------------------------------------------------
# reference engine (the executable spec; invariants checked every cycle)
# --------------------------------------------------------------------------

def simulate_packets_reference(wl: PacketWorkload,
                               record_links: Optional[np.ndarray] = None,
                               check: bool = True) -> PacketResult:
    """Pure-Python per-flit event loop over explicit per-link FIFO queues.

    Implements the five-phase cycle of the module docstring verbatim;
    with `check` (default) it additionally asserts the conservation
    invariants every cycle: no packet lost or duplicated across queues,
    every queue bounded by `capacity`, serialization clocks in
    [0, size], and the pending/in-network/delivered/dropped partition
    sums to the packet count.
    """
    e_num, p_num = wl.num_links, wl.num_packets
    q_cap, size = wl.capacity, wl.size
    rec = (np.asarray(record_links, dtype=np.int64)
           if record_links is not None else np.zeros(0, np.int64))
    queues: List[List[int]] = [[] for _ in range(e_num)]
    serve = np.zeros(e_num, dtype=np.int64)
    hop = np.zeros(p_num, dtype=np.int64)
    chosen = np.zeros(p_num, dtype=np.int64)
    ep_pkt = np.zeros(p_num, dtype=np.int64)
    ptr = wl.src_off[:-1].copy()
    deliver_t = np.zeros(p_num, dtype=np.int32)
    delivered = np.zeros(p_num, dtype=bool)
    dropped = np.zeros(p_num, dtype=bool)
    occ_sum = np.zeros(wl.cycles, dtype=np.int32)
    occ_max = np.zeros(wl.cycles, dtype=np.int32)
    occ_rec = np.zeros((wl.cycles, len(rec)), dtype=np.int32)
    eidx, hops, n_valid = wl.eidx, wl.hops, wl.n_valid
    gate = _gate_occ(q_cap)
    admitted = 0

    def _invariants(t: int) -> None:
        seen: List[int] = []
        for e in range(e_num):
            assert len(queues[e]) <= q_cap, f"queue {e} over capacity at {t}"
            seen.extend(queues[e])
        assert len(seen) == len(set(seen)), f"duplicated packet at {t}"
        in_net = len(seen)
        pending = int(sum(wl.src_off[1:] - ptr))
        done = int(delivered.sum()) + int(dropped.sum())
        assert pending + in_net + done == p_num, f"packet leak at cycle {t}"
        assert ((serve >= 0) & (serve <= size)).all()

    for t in range(wl.cycles):
        if t == wl.switch_cycle:
            _drop_failed_reference(wl, queues, serve, hop, chosen, ep_pkt,
                                   dropped)
        occ0 = [len(q) for q in queues]  # cycle-start occupancies
        # phase 1: serialization countdown
        for e in range(e_num):
            if occ0[e] and serve[e] > 0:
                serve[e] -= 1
        # phase 2: in-flight intents (upstream-link-id order)
        movers: Dict[int, List[Tuple[int, int]]] = {}
        exits: List[Tuple[int, int]] = []
        for e in range(e_num):
            if not occ0[e] or serve[e] != 0:
                continue
            pid = queues[e][0]
            nxt = int(eidx[ep_pkt[pid], wl.pkt_flow[pid], chosen[pid],
                           hop[pid] + 1])
            if nxt == e_num:
                exits.append((e, pid))
            else:
                movers.setdefault(nxt, []).append((e, pid))
        # phase 3: injection intents (one bid per source, FIFO per source)
        ep_now = 1 if t >= wl.switch_cycle else 0
        bids: Dict[int, Tuple[int, int, int]] = {}
        for s in range(wl.num_nodes):
            p = int(ptr[s])
            if p >= wl.src_off[s + 1] or wl.pkt_t[p] > t:
                continue
            f = int(wl.pkt_flow[p])
            if wl.adaptive:
                c = _decide_reference(wl, occ0, ep_now, f, gate)
            else:
                c = int(wl.pkt_cand[ep_now, p])
            tgt = int(eidx[ep_now, f, c, 0])
            assert tgt not in bids  # first links are source-distinct
            bids[tgt] = (s, p, c)
        # phase 4: arbitration + apply (in-flight first, then the bid)
        heads0 = {e: queues[e][0] for e in range(e_num) if queues[e]}
        for e, pid in exits:
            queues[e].pop(0)
            deliver_t[pid] = t
            delivered[pid] = True
        for tgt in sorted(set(movers) | set(bids)):
            free = q_cap - occ0[tgt]
            cands = movers.get(tgt, [])
            for e, pid in cands[:free]:
                queues[e].pop(0)
                queues[tgt].append(pid)
                hop[pid] += 1
            if tgt in bids and min(len(cands), free) < free:
                s, p, c = bids[tgt]
                queues[tgt].append(p)
                hop[p] = 0
                chosen[p] = c
                ep_pkt[p] = ep_now
                ptr[s] += 1
                admitted += 1
        # phase 5: head changes reset the serialization clock
        for e in range(e_num):
            head = queues[e][0] if queues[e] else p_num
            if head != heads0.get(e, p_num):
                serve[e] = size
        occ1 = np.array([len(q) for q in queues], dtype=np.int32)
        occ_sum[t] = occ1.sum()
        occ_max[t] = occ1.max() if e_num else 0
        if len(rec):
            occ_rec[t] = occ1[rec]
        if check:
            _invariants(t)
    return PacketResult(deliver_t=deliver_t, delivered=delivered,
                        dropped=dropped, inject_t=wl.pkt_t.copy(),
                        occ_sum=occ_sum, occ_max=occ_max, occ_rec=occ_rec,
                        cycles=wl.cycles, size=size, capacity=q_cap,
                        admitted=admitted)


def _decide_reference(wl: PacketWorkload, occ0: List[int], ep: int, f: int,
                      gate: int) -> int:
    """UGAL candidate choice: argmin over the valid prefix of
    hops + first-link occupancy (first index wins ties); UGAL_PF keeps
    the minimal candidate below the 2/3 gate."""
    eidx, hops = wl.eidx, wl.hops
    best_c, best_cost = 0, None
    for c in range(int(wl.n_valid[ep, f])):
        cost = int(hops[ep, f, c]) + occ0[int(eidx[ep, f, c, 0])]
        if best_cost is None or cost < best_cost:
            best_c, best_cost = c, cost
    if wl.gated and occ0[int(eidx[ep, f, 0, 0])] < gate:
        return 0
    return best_c


def _drop_failed_reference(wl: PacketWorkload, queues: List[List[int]],
                           serve: np.ndarray, hop: np.ndarray,
                           chosen: np.ndarray, ep_pkt: np.ndarray,
                           dropped: np.ndarray) -> None:
    """Failure switch: drop every in-network epoch-0 packet whose current
    or remaining hops cross a failed link (already-crossed links don't
    matter), compacting queues in order; changed heads restart their
    serialization clock."""
    for e in range(wl.num_links):
        if not queues[e]:
            continue
        head0 = queues[e][0]
        kept = []
        for pid in queues[e]:
            fh = int(wl.fail_hop[wl.pkt_flow[pid], chosen[pid]])
            hp = int(wl.hops[0, wl.pkt_flow[pid], chosen[pid]])
            if ep_pkt[pid] == 0 and hop[pid] <= fh < hp:
                dropped[pid] = True
            else:
                kept.append(pid)
        queues[e][:] = kept
        if (queues[e][0] if queues[e] else wl.num_packets) != head0:
            serve[e] = wl.size


# --------------------------------------------------------------------------
# batched engine (jit + lax.scan; vmapped over workload stacks)
# --------------------------------------------------------------------------

def _arrays(wl: PacketWorkload, record: np.ndarray) -> tuple:
    """Device-ready int32 views (padded where the scan gathers demand a
    safe slot: packet arrays get slot P, link arrays slot E)."""
    p = wl.num_packets
    pad1 = lambda a: jnp.asarray(  # noqa: E731
        np.concatenate([a.astype(np.int32), np.zeros(1, np.int32)]))
    return (jnp.asarray(wl.eidx), jnp.asarray(wl.hops),
            jnp.asarray(wl.n_valid), pad1(wl.pkt_flow),
            pad1(np.where(wl.pkt_t < wl.cycles, wl.pkt_t, wl.cycles)),
            jnp.asarray(np.concatenate(
                [wl.pkt_cand.astype(np.int32),
                 np.zeros((2, 1), np.int32)], axis=1)),
            jnp.asarray(wl.src_off.astype(np.int32)),
            jnp.asarray(wl.fail_hop), jnp.asarray(record.astype(np.int32)),
            jnp.asarray(np.int32(p)))


def _segment_ranks(st: jax.Array, e_num: int) -> tuple:
    """Arbitration ranks from sorted targets `st` (values in [0, e_num],
    e_num the dump target): each lane's rank within its run of equal
    targets, and each link's candidate count ([e_num]).  A rank is the
    position less the run's first position, a running max of the run
    starts; a count is the last lane's rank + 1, set at its link (the
    dump lane e_num takes the rest).  No search, no scatter-add."""
    pos = jnp.arange(st.shape[0], dtype=jnp.int32)
    step = st[1:] != st[:-1]
    first = jnp.concatenate([jnp.ones(1, bool), step])
    last = jnp.concatenate([step, jnp.ones(1, bool)])
    rank = pos - jax.lax.cummax(jnp.where(first, pos, 0))
    cnt = jnp.zeros(e_num + 1, jnp.int32).at[
        jnp.where(last & (st < e_num), st, e_num)].set(rank + 1)
    return rank, cnt[:e_num]


@functools.partial(
    jax.jit,
    static_argnames=("e_num", "size", "capacity", "adaptive", "gated",
                     "seg0", "seg1"))
def _run_batched(eidx, hops, n_valid, pkt_flow, pkt_t, pkt_cand, src_off,
                 fail_hop, record, p_num, *, e_num: int, size: int,
                 capacity: int, adaptive: bool, gated: bool, seg0: int,
                 seg1: int):
    """The whole run in one jit: scan epoch 0, apply the failure
    transform, scan epoch 1.  State is dense int32 arrays only -- queues
    [E + 1, Q] (row E absorbs rejected scatter lanes), per-link occ/serve,
    per-packet hop/chosen/epoch/outcome -- and every per-cycle update is
    gathers, one stable argsort (arbitration order), segmented ranks and
    per-link counts from the sorted targets (`_segment_ranks`), and
    unique-index `.at[].set` scatters.  No host syncs, no searches, no
    [n, n] anything, no scatter-add.  Device scopes of a cycle:
    `packet.route` (the per-hop and injection gathers),
    `packet.arbitrate` (the stable argsort, the segmented ranks and
    counts), `packet.queues` (the `.at[].set` scatters)."""
    q_cap = capacity
    p_pad = pkt_flow.shape[0] - 1  # static pad slot == P
    gate = _gate_occ(q_cap)

    def step(ep_now: int):
        def _step(state, t):
            queues, occ, serve, hop, chosen, ep_pkt, ptr, dlv_t, dlv = state
            with jax.named_scope("packet.route"):
                heads = queues[:e_num, 0]
                nonempty = occ > 0
                serve = jnp.where(nonempty & (serve > 0), serve - 1, serve)
                ready = nonempty & (serve == 0)
                # in-flight intents
                hf = pkt_flow[heads]
                nxt = eidx[ep_pkt[heads], hf, chosen[heads], hop[heads] + 1]
                exit_ = ready & (nxt == e_num)
                mover = ready & (nxt < e_num)
                tgt = jnp.where(mover, nxt, e_num)
                # injection intents (one bid per source; first links are
                # source-distinct, so bids never collide on a target)
                have = ptr < src_off[1:]
                bid_p = jnp.where(have, ptr, p_pad)
                pend = have & (pkt_t[bid_p] <= t)
                pf = pkt_flow[bid_p]
                occ_pad = jnp.concatenate([occ, jnp.zeros(1, jnp.int32)])
                if adaptive:
                    firsts = eidx[ep_now, pf, :, 0]          # [S, K]
                    cost = hops[ep_now, pf] + occ_pad[firsts]
                    k = eidx.shape[2]
                    ok = jnp.arange(k) < n_valid[ep_now, pf][:, None]
                    c = jnp.argmin(jnp.where(ok, cost, _BIG),
                                   axis=1).astype(jnp.int32)
                    if gated:
                        c = jnp.where(occ_pad[eidx[ep_now, pf, 0, 0]] >= gate,
                                      c, 0)
                else:
                    c = pkt_cand[ep_now, bid_p]
                itgt = jnp.where(pend, eidx[ep_now, pf, c, 0], e_num)
            with jax.named_scope("packet.arbitrate"):
                # arbitration: stable sort by target, rank within segment
                free = q_cap - occ
                order = jnp.argsort(tgt, stable=True)
                st = tgt[order]
                rank, cnt_cand = _segment_ranks(st, e_num)
                free_pad = jnp.concatenate([free, jnp.zeros(1, jnp.int32)])
                acc_s = (st < e_num) & (rank < free_pad[st])
                acc_cnt = jnp.minimum(cnt_cand, free)
                acc_cnt_pad = jnp.concatenate([acc_cnt,
                                               jnp.zeros(1, jnp.int32)])
                inj_acc = pend & (itgt < e_num) \
                    & (acc_cnt_pad[itgt] < free_pad[itgt])
            with jax.named_scope("packet.queues"):
                # apply: pops (exits + accepted movers) ...
                acc_lin = jnp.zeros(e_num, bool).at[order].set(acc_s)
                dep = exit_ | acc_lin
                dep_pad = jnp.concatenate([dep, jnp.zeros(1, bool)])
                shifted = jnp.concatenate(
                    [queues[:, 1:],
                     jnp.full((queues.shape[0], 1), p_pad, jnp.int32)], axis=1)
                queues = jnp.where(dep_pad[:, None], shifted, queues)
                occ_dep = occ - dep.astype(jnp.int32)
                occ_dep_pad = jnp.concatenate([occ_dep,
                                               jnp.zeros(1, jnp.int32)])
                # ... then pushes: movers land at base + rank, the bid after
                mrow = jnp.where(acc_s, st, e_num)
                mpos = jnp.clip(occ_dep_pad[st] + rank, 0, q_cap - 1)
                mpid = heads[order]
                queues = queues.at[mrow, mpos].set(
                    jnp.where(acc_s, mpid, queues[mrow, mpos]))
                irow = jnp.where(inj_acc, itgt, e_num)
                ipos = jnp.clip(occ_dep_pad[itgt] + acc_cnt_pad[itgt], 0,
                                q_cap - 1)
                queues = queues.at[irow, ipos].set(
                    jnp.where(inj_acc, bid_p, queues[irow, ipos]))
                inj_lin = jnp.zeros(e_num + 1, jnp.int32).at[irow].set(
                    inj_acc.astype(jnp.int32))
                occ = occ_dep + acc_cnt + inj_lin[:e_num]
                # per-packet bookkeeping (unique pids per scatter)
                hop = hop.at[jnp.where(acc_s, mpid, p_pad)].set(
                    hop[mpid] + 1)
                hop = hop.at[jnp.where(inj_acc, bid_p, p_pad)].set(0)
                chosen = chosen.at[jnp.where(inj_acc, bid_p, p_pad)].set(c)
                ep_pkt = ep_pkt.at[jnp.where(inj_acc, bid_p, p_pad)].set(
                    jnp.int32(ep_now))
                dpid = jnp.where(exit_, heads, p_pad)
                dlv_t = dlv_t.at[dpid].set(t)
                dlv = dlv.at[dpid].set(True)
                dlv = dlv.at[p_pad].set(False)
                ptr = ptr + inj_acc.astype(jnp.int32)
                # head changes restart serialization
                serve = jnp.where(queues[:e_num, 0] != heads, size, serve)
            return ((queues, occ, serve, hop, chosen, ep_pkt, ptr, dlv_t,
                     dlv),
                    (occ.sum(), jnp.max(occ, initial=0), occ[record]))
        return _step

    queues0 = jnp.full((e_num + 1, q_cap), p_pad, jnp.int32)
    state = (queues0, jnp.zeros(e_num, jnp.int32),
             jnp.zeros(e_num, jnp.int32),
             jnp.zeros(p_pad + 1, jnp.int32),
             jnp.zeros(p_pad + 1, jnp.int32),
             jnp.zeros(p_pad + 1, jnp.int32),
             src_off[:-1], jnp.zeros(p_pad + 1, jnp.int32),
             jnp.zeros(p_pad + 1, bool))
    state, ys0 = jax.lax.scan(step(0), state,
                              jnp.arange(seg0, dtype=jnp.int32))
    if seg1:
        # failure transform between the epochs
        queues, occ, serve, hop, chosen, ep_pkt, ptr, dlv_t, dlv = state
        pids = queues[:e_num]
        fq, cq = pkt_flow[pids], chosen[pids]
        fh = fail_hop[fq, cq]
        real = pids < p_num
        dropq = real & (ep_pkt[pids] == 0) & (fh >= hop[pids]) \
            & (fh < hops[0, fq, cq])
        keep = real & ~dropq
        heads0 = queues[:e_num, 0]
        qm = jnp.where(keep, pids, p_pad)
        ordk = jnp.argsort(dropq | ~real, axis=1, stable=True)
        qe = jnp.take_along_axis(qm, ordk, axis=1)
        queues = jnp.concatenate([qe, queues[e_num:]], axis=0)
        occ = keep.sum(axis=1).astype(jnp.int32)
        serve = jnp.where(qe[:, 0] != heads0, size, serve)
        dropped = jnp.zeros(p_pad + 1, bool).at[
            jnp.where(dropq, pids, p_pad).reshape(-1)].set(True)
        dropped = dropped.at[p_pad].set(False)
        state = (queues, occ, serve, hop, chosen, ep_pkt, ptr, dlv_t, dlv)
        state, ys1 = jax.lax.scan(
            step(1), state, jnp.arange(seg0, seg0 + seg1, dtype=jnp.int32))
        ys = tuple(jnp.concatenate([a, b]) for a, b in zip(ys0, ys1))
    else:
        dropped = jnp.zeros(p_pad + 1, bool)
        ys = ys0
    _, _, _, _, _, _, ptr, dlv_t, dlv = state
    admitted = (ptr - src_off[:-1]).sum()
    return dlv_t[:-1], dlv[:-1], dropped[:-1], admitted, ys


def simulate_packets(wl: PacketWorkload,
                     record_links: Optional[np.ndarray] = None,
                     engine: str = "auto") -> PacketResult:
    """Run a workload through the batched engine (`engine="batched"`,
    also the "auto" choice) or the scalar reference
    (`engine="reference"`).  Results are bit-identical."""
    if engine == "reference":
        return simulate_packets_reference(wl, record_links)
    if engine not in ("auto", "batched"):
        raise ValueError(f"unknown engine {engine!r}")
    rec = (np.asarray(record_links, dtype=np.int64)
           if record_links is not None else np.zeros(0, np.int64))
    if wl.num_packets == 0:
        z = np.zeros(wl.cycles, np.int32)
        return PacketResult(
            deliver_t=np.zeros(0, np.int32), delivered=np.zeros(0, bool),
            dropped=np.zeros(0, bool), inject_t=np.zeros(0, np.int32),
            occ_sum=z, occ_max=z.copy(),
            occ_rec=np.zeros((wl.cycles, len(rec)), np.int32),
            cycles=wl.cycles, size=wl.size, capacity=wl.capacity,
            admitted=0)
    seg0 = min(wl.switch_cycle, wl.cycles)
    dlv_t, dlv, dropped, admitted, ys = _run_batched(
        *_arrays(wl, rec), e_num=wl.num_links, size=wl.size,
        capacity=wl.capacity, adaptive=wl.adaptive, gated=wl.gated,
        seg0=seg0, seg1=wl.cycles - seg0)
    return PacketResult(
        deliver_t=np.asarray(dlv_t), delivered=np.asarray(dlv),
        dropped=np.asarray(dropped), inject_t=wl.pkt_t.copy(),
        occ_sum=np.asarray(ys[0], dtype=np.int32),
        occ_max=np.asarray(ys[1], dtype=np.int32),
        occ_rec=np.asarray(ys[2], dtype=np.int32).reshape(wl.cycles,
                                                          len(rec)),
        cycles=wl.cycles, size=wl.size, capacity=wl.capacity,
        admitted=int(admitted))


def simulate_packets_batch(wls: Sequence[PacketWorkload]
                           ) -> List[PacketResult]:
    """vmap a stack of same-shape workloads (seed replicas, burst-phase
    replicas) through the batched engine in one dispatch.  All workloads
    must share static config and array shapes (same graph / mode /
    cycles / packet count -- pad or resample to equalize counts)."""
    if not wls:
        return []
    w0 = wls[0]
    for w in wls[1:]:
        if (w.num_links, w.num_packets, w.cycles, w.size, w.capacity,
                w.mode, w.switch_cycle, w.eidx.shape) != \
           (w0.num_links, w0.num_packets, w0.cycles, w0.size, w0.capacity,
                w0.mode, w0.switch_cycle, w0.eidx.shape):
            raise ValueError("simulate_packets_batch needs same-shape "
                             "workloads")
    rec = np.zeros(0, np.int64)
    stacks = [jnp.stack(cols) for cols in
              zip(*(_arrays(w, rec) for w in wls))]
    run = functools.partial(
        _run_batched, e_num=w0.num_links, size=w0.size,
        capacity=w0.capacity, adaptive=w0.adaptive, gated=w0.gated,
        seg0=min(w0.switch_cycle, w0.cycles),
        seg1=w0.cycles - min(w0.switch_cycle, w0.cycles))
    dlv_t, dlv, dropped, admitted, ys = jax.vmap(run)(*stacks)
    out = []
    for i, w in enumerate(wls):
        out.append(PacketResult(
            deliver_t=np.asarray(dlv_t[i]), delivered=np.asarray(dlv[i]),
            dropped=np.asarray(dropped[i]), inject_t=w.pkt_t.copy(),
            occ_sum=np.asarray(ys[0][i], dtype=np.int32),
            occ_max=np.asarray(ys[1][i], dtype=np.int32),
            occ_rec=np.zeros((w.cycles, 0), np.int32),
            cycles=w.cycles, size=w.size, capacity=w.capacity,
            admitted=int(admitted[i])))
    return out
