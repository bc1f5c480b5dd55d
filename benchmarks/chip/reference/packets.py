"""Plain reference for the packet layer: a cycle-driven packet engine on
explicit per-link FIFO queues, vectorized over links with numpy.

The model is the one the program documents: every directed link has a
FIFO output queue of `capacity` packets; the head packet serializes for
`size` cycles (one flit a cycle) before it may move; moving needs a free
slot in the next link's queue, counted at the start of the cycle (a slot
freed in a cycle is usable in the next).  Each cycle:

1. every non-empty link's head counts its serialization down (floor 0);
   heads at 0 are ready;
2. a ready head whose path is done leaves the network (delivered);
   otherwise it asks for its path's next link;
3. each source router offers its oldest pending packet (arrival cycle,
   then flow id) to the first link of a candidate path: under UGAL the
   valid candidate with the least hops + cycle-start occupancy of its
   first link, the lowest index on ties;
4. each link gives its `capacity - occupancy` free slots to the packets
   asking for it in increasing upstream-link id, then to the source's
   offer if a slot is left; the accepted packets join the queue's tail in
   that order, the others wait;
5. a queue whose head changed starts the new head's serialization.
"""

from __future__ import annotations

import numpy as np


def simulate(paths: np.ndarray, hops: np.ndarray, valid: np.ndarray,
             src_of_flow: np.ndarray, pkt_flow: np.ndarray,
             pkt_t: np.ndarray, num_links: int, size: int, capacity: int,
             cycles: int, adaptive: bool, pkt_cand: np.ndarray = None):
    """Run the packets.  `paths` [F, K, L] directed link ids (-1 padded),
    `hops` [F, K], `valid` [F, K]; packets are (flow, arrival cycle) pairs;
    `pkt_cand` gives oblivious modes their pre-drawn candidate.  Returns
    (delivered [P] bool, deliver_t [P] int, admitted) in the order of the
    packets given."""
    e_num = int(num_links)
    p_num = len(pkt_flow)
    f_num, k_num, l_num = paths.shape
    pk = np.where(paths >= 0, paths, e_num).astype(np.int64)
    pk = np.concatenate([pk, np.full((f_num, k_num, 1), e_num)], axis=2)
    first = pk[:, :, 0]
    # per-source FIFO: order by (source, arrival cycle, flow, given order)
    src = src_of_flow[pkt_flow]
    order = np.lexsort((np.arange(p_num), pkt_flow, pkt_t, src))
    n_src = int(src_of_flow.max()) + 1
    start = np.searchsorted(src[order], np.arange(n_src + 1))
    ptr = start[:-1].copy()

    queue = np.full((e_num, capacity), -1, dtype=np.int64)
    occ = np.zeros(e_num, dtype=np.int64)
    serve = np.zeros(e_num, dtype=np.int64)
    hop = np.zeros(p_num, dtype=np.int64)
    chosen = np.zeros(p_num, dtype=np.int64)
    delivered = np.zeros(p_num, dtype=bool)
    deliver_t = np.zeros(p_num, dtype=np.int64)
    admitted = 0
    for t in range(cycles):
        occ0 = occ.copy()
        head0 = queue[:, 0].copy()
        busy = occ0 > 0
        serve = np.where(busy & (serve > 0), serve - 1, serve)
        ready = np.flatnonzero(busy & (serve == 0))
        pid = head0[ready]
        nxt = pk[pkt_flow[pid], chosen[pid], hop[pid] + 1]
        leaving = ready[nxt == e_num]
        delivered[head0[leaving]] = True
        deliver_t[head0[leaving]] = t
        mv_from, mv_to = ready[nxt < e_num], nxt[nxt < e_num]
        # source offers
        have = np.flatnonzero(ptr < start[1:])
        cand_pid = order[ptr[have]]
        due = pkt_t[cand_pid] <= t
        have, cand_pid = have[due], cand_pid[due]
        fl = pkt_flow[cand_pid]
        if adaptive:
            cost = hops[fl] + occ0[first[fl]]
            cost = np.where(valid[fl], cost, np.iinfo(np.int64).max)
            c = np.argmin(cost, axis=1)
        else:
            c = pkt_cand[cand_pid]
        off_to = first[fl, c]
        # arbitration: movers by (target, upstream id), then the offer
        o = np.lexsort((mv_from, mv_to))
        mv_from, mv_to = mv_from[o], mv_to[o]
        rank = np.arange(len(mv_to)) - np.searchsorted(mv_to, mv_to)
        free = capacity - occ0
        acc = rank < free[mv_to]
        n_acc = np.bincount(mv_to[acc], minlength=e_num)
        off_ok = n_acc[off_to] < free[off_to]
        # pops: delivered heads and accepted movers
        gone = np.zeros(e_num, dtype=bool)
        gone[leaving] = True
        gone[mv_from[acc]] = True
        queue[gone, :-1] = queue[gone, 1:]
        queue[gone, -1] = -1
        occ = occ - gone
        # pushes: accepted movers in arbitration order, then the offers
        mp = head0[mv_from[acc]]
        mt = mv_to[acc]
        queue[mt, occ[mt] + rank[acc]] = mp
        hop[mp] += 1
        occ = occ + n_acc
        ip, it, ic = cand_pid[off_ok], off_to[off_ok], c[off_ok]
        queue[it, occ[it]] = ip
        occ[it] += 1
        chosen[ip] = ic
        hop[ip] = 0
        ptr[have[off_ok]] += 1
        admitted += len(ip)
        changed = queue[:, 0] != head0
        serve[changed] = size
        if (occ > capacity).any() or (occ != (queue >= 0).sum(axis=1)).any():
            raise AssertionError(f"queue invariant broken at cycle {t}")
    in_net = int(occ.sum())
    if int(delivered.sum()) + in_net != admitted:
        raise AssertionError("packets not conserved")
    return delivered, deliver_t, admitted
