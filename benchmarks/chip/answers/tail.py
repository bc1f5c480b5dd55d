"""Answer kind `tail`: packet latency tails of a deployment.

One answer starts from the graph: PolarFly ER_q, the blocked routing, the
candidate paths, the packet workload (`make_workload`), then the packet
engine `simulate_packets`; its per-packet outcomes come back to the host,
where the tails are taken over the packets that arrived after the first
`skip_cycles` cycles (nearest rank), with the packets not delivered by the
horizon counted beside them.

The check compares the last answer of the window with the plain
references (`reference/graph.py`, `reference/packets.py`):

* `paths_bad`: candidate paths that differ from the reference's; exact;
* `arrivals_bad`: flows whose offered packets differ from the ones the
  benchmark derives from the seed itself (`offered_packets`); exact;
* `packets_bad`: packets whose outcome (delivered or not, and when)
  differs from the reference engine's, run on the reference's paths and
  the benchmark's own arrivals; exact.
"""

from __future__ import annotations

import os
import sys

import numpy as np

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)

import harness  # noqa: E402
from reference import packets as ref_packets  # noqa: E402

TIME_METRIC = "tail_answer_s"


def _sat():
    return harness.load_module("answers", "sat")


def answer(cfg: dict, mix: dict, inputs: dict, span) -> dict:
    from repro.simulation.packet import make_workload, simulate_packets

    prm = mix["params"]
    with span("answer"):
        fp = _sat().build_paths(cfg, mix, inputs, span)
        with span("workload"):
            wl = make_workload(fp, prm["offered"], prm["cycles"],
                               size=int(cfg["packet_flits"]),
                               capacity=int(cfg["queue_packets"]),
                               seed=inputs["program_seed"],
                               max_packets=int(prm["max_packets"]))
        with span("scan"):
            res = simulate_packets(wl)
        with span("readback"):
            late = wl.pkt_t >= prm["skip_cycles"]
            lat = (res.deliver_t - wl.pkt_t)[late & res.delivered]
            tails = harness.tail_percentiles(lat) if len(lat) else {}
            undelivered = int((late & ~res.delivered).sum())
    return {"tails": tails, "undelivered": undelivered,
            "admitted": int(res.admitted), "packets": int(wl.num_packets),
            "pkt_flow": wl.pkt_flow, "pkt_t": wl.pkt_t,
            "delivered": res.delivered, "deliver_t": res.deliver_t,
            "edges": fp.edges, "hops": fp.hops, "valid": fp.valid,
            "num_links": int(fp.num_links),
            "num_flows": int(fp.edges.shape[0])}


def rate(cfg: dict, mix: dict) -> float:
    """Packets a flow offers per cycle: offered load x p flits / size."""
    return mix["params"]["offered"] * cfg["p"] / cfg["packet_flits"]


def offered_packets(cfg: dict, mix: dict, inputs: dict):
    """(pkt_flow, pkt_t) the traffic offers, in per-source FIFO order
    (source router, arrival cycle, flow), as the benchmark derives them
    from the seed.  Flow f earns r packets a cycle (`rate`) on a credit
    accumulator that starts at a phase in [0, 1) and offers a packet each
    time it crosses a whole number.  The phases are the first F draws of
    the generator whose seed the benchmark hands the program
    (`make_workload` documents that its phases are its first draws)."""
    src = np.asarray(inputs["src"], np.int64)
    n, cycles = len(src), int(mix["params"]["cycles"])
    phase = np.random.default_rng(inputs["program_seed"]).random(n)
    credit = phase[:, None] + np.cumsum(np.full((n, cycles),
                                                rate(cfg, mix)), axis=1)
    due = np.diff(np.floor(credit).astype(np.int64), axis=1, prepend=0)
    fi, ti = np.nonzero(due)
    flow = np.repeat(fi, due[fi, ti])
    t = np.repeat(ti, due[fi, ti])
    order = np.lexsort((flow, t, src[flow]))
    return flow[order], t[order]


def arrivals_bad(got_flow, got_t, want_flow, want_t) -> int:
    """Flows whose offered packets (how many arrive in which cycle)
    differ between the program's workload and the benchmark's own."""
    def counted(flow, t):
        key = np.asarray(flow, np.int64) * (1 << 32) + np.asarray(t)
        k, c = np.unique(key, return_counts=True)
        return set(zip(k.tolist(), c.tolist()))
    differ = counted(got_flow, got_t) ^ counted(want_flow, want_t)
    return len({k >> 32 for k, _ in differ})


def reference_outcomes(cfg: dict, mix: dict, inputs: dict, got: dict,
                       ref_edges: np.ndarray, offered: tuple,
                       size: int = None):
    """The reference engine's (delivered, deliver_t, admitted) of the
    `offered` packets on the reference's own paths."""
    hops = (ref_edges >= 0).sum(axis=2)
    return ref_packets.simulate(
        ref_edges, hops, hops > 0, np.asarray(inputs["src"]),
        offered[0], offered[1], got["num_links"],
        int(size or cfg["packet_flits"]),
        int(cfg["queue_packets"]), int(mix["params"]["cycles"]),
        adaptive=cfg["routing"] in ("ugal", "ugal_pf"))


def outcomes_bad(got: dict, offered: tuple, delivered, deliver_t) -> int:
    """Packets whose outcome (delivered or not, and when) differs from the
    reference's, packet by packet in FIFO order; where the program offered
    other packets than `offered`, every packet past the first difference
    counts."""
    d = np.asarray(got["delivered"])
    t = np.asarray(got["deliver_t"])
    n = min(len(d), len(delivered))
    same = ((np.asarray(got["pkt_flow"])[:n] == offered[0][:n])
            & (np.asarray(got["pkt_t"])[:n] == offered[1][:n]))
    n = int(np.argmin(same)) if not same.all() else n
    differ = (d[:n] != delivered[:n]) | (d[:n] & (t[:n] != deliver_t[:n]))
    return int(differ.sum()) + max(len(d), len(delivered)) - n


def check(cfg: dict, mix: dict, inputs: dict, got: dict,
          limits: dict) -> dict:
    bad, ref_edges, _ = _sat().reference_paths(cfg, inputs, got)
    checks = {"paths_bad": {"value": bad, "limit": 0}}
    offered = offered_packets(cfg, mix, inputs)
    checks["arrivals_bad"] = {
        "value": arrivals_bad(got["pkt_flow"], got["pkt_t"], *offered),
        "limit": 0}
    if ref_edges is None:  # wrong link space or flow count
        return checks
    delivered, deliver_t, _ = reference_outcomes(cfg, mix, inputs, got,
                                                 ref_edges, offered)
    checks["packets_bad"] = {"value": outcomes_bad(got, offered, delivered,
                                                   deliver_t),
                             "limit": limits["packets_bad"]}
    return checks


def control(cfg: dict, mix: dict, inputs: dict, got: dict, limits: dict,
            name: str = None) -> dict:
    """The check's numbers for the control, "three_flit_packets": the
    reference engine with a packet serializing for one cycle less per hop
    than its stated 4 flits (one stated guarantee broken) in the
    program's place."""
    name = name or limits["control"]
    if name != "three_flit_packets":
        raise ValueError(f"unknown control {name!r}")
    _, ref_edges, _ = _sat().reference_paths(cfg, inputs, got)
    offered = offered_packets(cfg, mix, inputs)
    want = reference_outcomes(cfg, mix, inputs, got, ref_edges, offered)
    ctrl = reference_outcomes(cfg, mix, inputs, got, ref_edges, offered,
                              size=int(cfg["packet_flits"]) - 1)
    ctrl_got = dict(got, pkt_flow=offered[0], pkt_t=offered[1],
                    delivered=ctrl[0], deliver_t=ctrl[1])
    return {"packets_bad": {"value": outcomes_bad(ctrl_got, offered,
                                                  want[0], want[1]),
                            "limit": limits["packets_bad"]}}
