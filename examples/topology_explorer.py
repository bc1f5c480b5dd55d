"""Compare PolarFly against Slim Fly / Dragonfly / Jellyfish: saturation
under uniform + adversarial traffic, bisection, and resilience.

  PYTHONPATH=src python examples/topology_explorer.py

Under BENCH_SMOKE=1 the table shrinks to PF(7)/DF(4,2) and a reduced
Frank-Wolfe budget, so the script runs in seconds (this is what CI
executes).  Path construction and the fluid solver run on their default
engines (`engine="auto"` / batched); the adaptive column also reports the
solver's own truncation-error estimate (`truncation_error`, see
docs/benchmarks.md) so you can tell whether the iteration budget was
enough.
"""
import os

from repro.core import topologies as tp
from repro.core.metrics import bisection_fraction, resilience_sweep
from repro.core.polarfly import build_polarfly
from repro.core.routing import build_routing
from repro.simulation import (build_flow_paths, make_pattern,
                              saturation_throughput, truncation_error)


def main():
    smoke = os.environ.get("BENCH_SMOKE", "0") not in ("", "0")
    if smoke:
        graphs = {
            "PolarFly(7)": (build_polarfly(7).graph, build_polarfly(7)),
            "Dragonfly(4,2)": (tp.build_dragonfly(4, 2), None),
        }
        iters = 300
    else:
        graphs = {
            "PolarFly(13)": (build_polarfly(13).graph, build_polarfly(13)),
            "SlimFly(9)": (tp.build_slimfly(9), None),
            "Dragonfly(6,3)": (tp.build_dragonfly(6, 3), None),
            "Jellyfish(183,14)": (tp.build_jellyfish(183, 14, seed=0), None),
        }
        # convergence-grade budget for the adaptive equilibrium (see the
        # truncation-noise discussion in docs/benchmarks.md)
        iters = 1500
    print(f"{'topology':20s} {'N':>5s} {'radix':>5s} {'unif(min)':>9s} "
          f"{'adv(min)':>8s} {'adv(UGAL)':>9s} {'fw_err':>7s} "
          f"{'bisect':>7s} {'diam@20%fail':>12s}")
    for name, (g, pf) in graphs.items():
        rt = build_routing(g, pf)  # engine="auto"
        p = max(2, g.params.get("radix", 8) // 2)
        uni = make_pattern("uniform", rt, p=p, seed=0)
        adv = make_pattern("random_perm", rt, p=p, seed=0)
        s_uni = saturation_throughput(build_flow_paths(rt, uni, "min"), tol=0.02)
        s_adv = saturation_throughput(build_flow_paths(rt, adv, "min"), tol=0.02)
        fp_ug = build_flow_paths(rt, adv, "ugal", k_candidates=10)
        s_ug = saturation_throughput(fp_ug, tol=0.02, iters=iters)
        fw_err = truncation_error(fp_ug, s_ug, iters)
        bis = bisection_fraction(g)
        res = resilience_sweep(g, [0.2], seed=0)[0].diameter
        print(f"{name:20s} {g.n:5d} {g.params.get('radix','?'):>5} "
              f"{s_uni:9.3f} {s_adv:8.3f} {s_ug:9.3f} "
              f"{fw_err:7.4f} {bis:7.3f} {res:12d}")


if __name__ == "__main__":
    main()
