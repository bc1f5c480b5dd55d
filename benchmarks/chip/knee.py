#!/usr/bin/env python3
"""Packet knee sweep: accepted against offered load, with source backlog.

    python3 benchmarks/chip/knee.py --workload pf31_ugal.tail --seed 1 \
        --loads 0.1 0.2 0.3

For one seed's deployment of the cell's configuration, builds the paths
once and runs the packet engine at each offered load for the traffic's
`cycles`.  Per load, one JSON line: the accepted load (flits delivered per
cycle per endpoint, over the cycles after `skip_cycles`, as a fraction of
unit load), the packets still waiting at their sources at the horizon,
the tails and the packets not delivered.  Run once on the chip to place
the tail mix's offered load below the knee.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import harness  # noqa: E402


def main(argv=None, require_tpu: bool = True, config: dict = None,  # reprolint: allow[naked-clock] -- times whole packet runs whose outcomes are read back to the host
         traffic: dict = None) -> list:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--loads", type=float, nargs="+", required=True)
    args = ap.parse_args(argv)
    cell = harness.workload_entry(harness.benchmark_spec(), args.workload)
    cfg = config or harness.load_config(cell["config"])
    mix = traffic or harness.load_traffic(cell["traffic"])
    if require_tpu:
        harness.use_compile_cache()
        harness.require_chips(cell["chips"])
    harness.add_program()
    from repro.simulation.packet import make_workload, simulate_packets

    sat = harness.load_module("answers", "sat")
    prm = mix["params"]
    inputs = harness.draw_inputs(cfg["N"], args.seed)
    fp = sat.build_paths(cfg, mix, inputs, harness.Spans())
    cyc, skip = int(prm["cycles"]), int(prm["skip_cycles"])
    out = []
    for lam in args.loads:
        t0 = time.perf_counter()
        wl = make_workload(fp, lam, cyc, size=int(cfg["packet_flits"]),
                           capacity=int(cfg["queue_packets"]),
                           seed=inputs["program_seed"],
                           max_packets=50_000_000)
        res = simulate_packets(wl)
        late = wl.pkt_t >= skip
        got = res.delivered & (res.deliver_t >= skip)
        accepted = (got.sum() * cfg["packet_flits"]
                    / ((cyc - skip) * cfg["N"] * cfg["p"]))
        lat = (res.deliver_t - wl.pkt_t)[late & res.delivered]
        rec = {"offered": lam, "accepted": float(accepted),
               "source_backlog": int(wl.num_packets - res.admitted),
               "in_network": int(res.admitted - res.num_delivered),
               "packets": int(wl.num_packets),
               "undelivered_late": int((late & ~res.delivered).sum()),
               "tails": harness.tail_percentiles(lat) if len(lat) else {},
               "seconds": time.perf_counter() - t0}
        print(json.dumps(rec), flush=True)
        out.append(rec)
    return out


if __name__ == "__main__":
    main()
