#!/usr/bin/env python3
"""Readings that set the limits of a cell's check, on the chip.

    python3 benchmarks/chip/readings.py --workload <name> --seeds 1 2 3 \
        [--controls cell bfloat16]

For each seed, in one process: one answer of the timed path at the cell's
own size, and the numbers its check compares (the lower readings); with
`--controls`, also the same numbers for each named control in the
program's place (the upper readings; `cell` is the control the cell's
limits file names).  One JSON line per seed; without a TPU it exits
non-zero.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import harness  # noqa: E402


def main(argv=None, require_tpu: bool = True, config: dict = None,  # reprolint: allow[naked-clock] -- times whole answers and host-side checks, each read back to the host
         traffic: dict = None) -> list:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--controls", nargs="*", default=[])
    args = ap.parse_args(argv)
    spec = harness.benchmark_spec()
    cell = harness.workload_entry(spec, args.workload)
    cfg = config or harness.load_config(cell["config"])
    mix = traffic or harness.load_traffic(cell["traffic"])
    limits = harness.load_limits(args.workload)
    kind = harness.load_module("answers", mix["answer"])
    if require_tpu:
        harness.use_compile_cache()
        device = harness.require_chips(cell["chips"])
    else:
        device = harness.device_record(cell["chips"])
    harness.add_program()
    out = []
    for seed in args.seeds:
        # the deployment a run on this seed checks: its window's last
        inputs = harness.run_inputs(cfg["N"], mix, seed)[-1]
        t0 = time.perf_counter()
        got = kind.answer(cfg, mix, inputs, harness.Spans())
        t1 = time.perf_counter()
        rec = {"workload": args.workload, "seed": seed, "device": device,
               "program_seed": inputs["program_seed"],
               "answer_s": t1 - t0,
               "program": {k: c["value"] for k, c in
                           kind.check(cfg, mix, inputs, got, limits).items()}}
        rec["check_s"] = time.perf_counter() - t1
        for name in args.controls:
            name = limits["control"] if name == "cell" else name
            t2 = time.perf_counter()
            rec[f"control.{name}"] = {
                k: c["value"] for k, c in
                kind.control(cfg, mix, inputs, got, limits, name).items()}
            rec[f"control_s.{name}"] = time.perf_counter() - t2
        for k in ("value", "sat_lo", "sat_hi", "iters", "tails",
                  "undelivered", "admitted", "packets"):
            if k in got:
                rec[k] = got[k]
        print(json.dumps(rec), flush=True)
        out.append(rec)
    return out


if __name__ == "__main__":
    main()
