"""Answer kind `sat`: the certified saturation throughput of a deployment.

One answer starts from the graph: PolarFly ER_q, the blocked routing with
its BFS on the chip, the candidate paths of the traffic, then the public
certified entry `saturation_throughput(..., certify=True)`, whose floats
come back to the host.  Nothing is reused between answers but compiled
programs.

The check compares the last answer of the window with the plain
references (`reference/graph.py`, `reference/fluid.py`).  The reference
saturation is an interval [lo, hi] (its continuation's last feasible and
first infeasible loads); each number is a distance between intervals:

* `paths_bad`: candidate paths (flow, slot) that differ from the
  reference's minimal / Valiant construction, over every flow; exact;
* `sat_off`: how far the reference interval lies from the answer's
  bisection cell [value, value + 2^-probes];
* `bracket_off`: how far it lies from the certified bracket
  [sat_lo, sat_hi].
"""

from __future__ import annotations

import math
import os
import sys

import numpy as np

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)

from reference import fluid as ref_fluid  # noqa: E402
from reference import graph as ref_graph  # noqa: E402

TIME_METRIC = "sat_answer_s"


def k_candidates(cfg: dict) -> int:
    """Valiant candidates beside the minimal path (K - 1)."""
    return int(cfg["K"]) - 1


def probes(mix: dict) -> int:
    return max(1, int(math.ceil(math.log2(1.0 / mix["params"]["tol"]))))


def build_paths(cfg: dict, mix: dict, inputs: dict, span):
    """graph -> routing -> paths, each in its span; returns the program's
    candidate paths (FlowPaths) of the inputs' flows, with their device
    arrays on the chip."""
    import jax
    from repro.core.polarfly import build_polarfly
    from repro.core.routing import build_blocked_routing
    from repro.simulation.paths import build_flow_paths
    from repro.simulation.traffic import TrafficPattern

    with span("graph"):
        g = build_polarfly(int(cfg["q"])).graph
    with span("routing"):
        rt = build_blocked_routing(g, backend="sharded", devices=1)
    with span("paths"):
        n = len(inputs["src"])
        pat = TrafficPattern(mix["pattern"], inputs["src"], inputs["dst"],
                             np.full(n, float(cfg["p"]), np.float32),
                             int(cfg["p"]))
        fp = build_flow_paths(rt, pat, cfg["routing"],
                              k_candidates=k_candidates(cfg),
                              seed=inputs["program_seed"])
        jax.block_until_ready(fp.device_arrays())
    return fp


def answer(cfg: dict, mix: dict, inputs: dict, span) -> dict:
    from repro.simulation.fluid import saturation_throughput

    with span("answer"):
        fp = build_paths(cfg, mix, inputs, span)
        with span("solve"):
            res = saturation_throughput(fp, **mix["params"])
    c = res.cert
    return {"value": float(res.value), "sat_lo": float(res.sat_lo),
            "sat_hi": float(res.sat_hi), "iters": int(c.iters),
            "gap": float(c.gap), "util_lb": float(c.util_lb),
            "util_ub": float(c.util_ub), "converged": bool(c.converged),
            "edges": fp.edges, "hops": fp.hops, "valid": fp.valid,
            "num_links": int(fp.num_links), "num_flows": int(fp.edges.shape[0])}


def interval_gap(a_lo: float, a_hi: float, b_lo: float, b_hi: float) -> float:
    """How far apart the intervals [a_lo, a_hi] and [b_lo, b_hi] lie (0
    where they meet)."""
    return max(0.0, a_lo - b_hi, b_lo - a_hi)


def reference_paths(cfg: dict, inputs: dict, got: dict):
    """(paths_bad, reference candidate edges of every flow, graph)."""
    g = ref_graph.polarfly(int(cfg["q"]))
    n = g.n
    if got["num_links"] != g.num_links or got["num_flows"] != n:
        bad = max(1, abs(got["num_flows"] - n))
        return bad, None, g
    mode = "min" if cfg["routing"] == "min" else "ugal"
    dist = ref_graph.distance_table(g, inputs["dst"] if mode == "min"
                                    else None)
    bad, ref_edges = ref_graph.check_paths(
        g, inputs["src"], inputs["dst"], got["edges"], got["hops"],
        got["valid"], mode, np.arange(n), dist)
    return bad, ref_edges, g


def reference_saturation(cfg: dict, ref_edges, g,
                         dtype: str = "float64"):
    """(lo, hi) of the reference equilibrium's saturation."""
    eq = ref_fluid.Equilibrium(ref_edges, ref_edges[:, :, 0] >= 0,
                               np.full(g.n, float(cfg["p"])), g.num_links,
                               dtype)
    return eq.saturation()


def compare(mix: dict, got: dict, ref: tuple, lim: dict) -> dict:
    lo, hi = ref
    step = 2.0 ** -probes(mix)
    v = got["value"]
    cell_hi = v if v >= 1.0 else v + step
    return {
        "sat_off": {"value": interval_gap(lo, hi, v, cell_hi),
                    "limit": lim["sat_off"]},
        "bracket_off": {"value": interval_gap(lo, hi, got["sat_lo"],
                                              got["sat_hi"]),
                        "limit": lim["bracket_off"]},
    }


def check(cfg: dict, mix: dict, inputs: dict, got: dict,
          limits: dict) -> dict:
    bad, ref_edges, g = reference_paths(cfg, inputs, got)
    checks = {"paths_bad": {"value": bad, "limit": 0}}
    if ref_edges is None:  # wrong link space or flow count: nothing to solve
        return checks
    checks.update(compare(mix, got, reference_saturation(cfg, ref_edges, g),
                          limits))
    return checks


def control(cfg: dict, mix: dict, inputs: dict, got: dict, limits: dict,
            name: str = None) -> dict:
    """The check's numbers for a control in the program's place; `name`,
    else the cell's limits file, says which:

    * "bfloat16": the reference computed in bfloat16; its answer is the
      bisection cell that holds its saturation, its bracket its own
      [lo, hi];
    * "one_probe_fewer": the float64 reference read on a bisection grid
      one probe coarser than the mix's `tol` states (its answer and its
      bracket are the coarser cell), which breaks the stated resolution.
    """
    name = name or limits["control"]
    _, ref_edges, g = reference_paths(cfg, inputs, got)
    ref = reference_saturation(cfg, ref_edges, g)
    step = 2.0 ** -probes(mix)
    if name == "bfloat16":
        c_lo, c_hi = reference_saturation(cfg, ref_edges, g, "bfloat16")
    elif name == "one_probe_fewer":
        step *= 2.0
        c_lo = 1.0 if ref[0] >= 1.0 else math.floor(ref[0] / step) * step
        c_hi = min(1.0, c_lo + step)
    else:
        raise ValueError(f"unknown control {name!r}")
    value = 1.0 if c_lo >= 1.0 else math.floor(c_lo / step) * step
    return compare(mix, dict(got, value=value, sat_lo=c_lo, sat_hi=c_hi),
                   ref, limits)
