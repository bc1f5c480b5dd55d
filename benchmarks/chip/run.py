#!/usr/bin/env python3
"""Run one cell of the chip benchmark once.

    python3 benchmarks/chip/run.py --workload <name> --seed <n> \
        --seconds <s> --trace <0|1>

from the root of a checkout, on a machine with a TPU.  In order:

1. set-up: the run's deployments (`harness.run_inputs`: one drawn from
   the seed, or the traffic mix's fixed pool in an order drawn from it),
   and one whole answer of each, which compiles every program the window
   uses (JAX's persistent cache lives in the checkout);
2. the window: the deployments answered in turn, back to back for
   `--seconds` in whole passes, each answer from the graph up (graph,
   routing, paths, the public entry, read-back);
3. with `--trace 1`, one more answer under the JAX profiler, whose trace
   gives the device busy time, the kernels' shares of their roofline and
   the host span open during each idle gap;
4. the check: the window's last answer against the plain references under
   ``reference/``, each compared number printed beside its limit.

The last line of standard output is one JSON object (`correct`,
`attempted`, `failed`, `metrics`, `device`, with `--trace 1` also
`breakdown`, and last `checks`).  Without a TPU, or with fewer chips than
the cell needs, the run exits non-zero and prints no result.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import time

T_START = time.perf_counter()  # reprolint: allow[naked-clock] -- set-up starts at process start; the warm-up answer blocks on its results

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import harness  # noqa: E402


def main(argv=None, require_tpu: bool = True, spec: dict = None,  # reprolint: allow[naked-clock] -- set-up ends after the warm-up answer, which blocks on its results
         config: dict = None, traffic: dict = None,
         limits: dict = None) -> dict:
    """One run; returns the result record it printed.  `require_tpu`,
    `spec`, `config`, `traffic` and `limits` exist for the CPU tests, which
    drive a run at a tiny size without a chip."""
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    spec = spec or harness.benchmark_spec()
    cell = harness.workload_entry(spec, args.workload)
    cfg = config or harness.load_config(cell["config"])
    mix = traffic or harness.load_traffic(cell["traffic"])
    kind = harness.load_module("answers", mix["answer"])

    if require_tpu:
        harness.use_compile_cache()
    mon = harness.CompileMonitor()
    device = (harness.require_chips(cell["chips"]) if require_tpu
              else harness.device_record(cell["chips"]))
    harness.add_program()

    # ---- set-up: inputs from the seed, one whole answer --------------------
    runs = harness.run_inputs(cfg["N"], mix, args.seed)
    for inputs in runs:
        kind.answer(cfg, mix, inputs, harness.Spans())
    setup_s = time.perf_counter() - T_START
    print(f"setup_s={setup_s!r} compile_s={mon.compile_s!r} "
          f"compiles={mon.compiles} cache_hits={mon.hits} "
          f"cache_misses={mon.misses}", flush=True)

    # ---- the window --------------------------------------------------------
    spans = harness.Spans()
    win = harness.Window(args.seconds, len(runs))
    m0, h0 = mon.misses, mon.hits
    win.run(lambda i: kind.answer(cfg, mix, runs[i % len(runs)], spans))
    # every program goes through the persistent cache, so a miss is a
    # compile; a hit is a program the simulator traced anew and loaded
    print(f"window answers={len(win.results)} elapsed_s={win.elapsed!r} "
          f"per_answer_s={win.per_answer_s!r} "
          f"window_compiles={mon.misses - m0} "
          f"window_cache_hits={mon.hits - h0}", flush=True)
    mem = harness.memory_peak_bytes(cell["chips"]) if require_tpu else None
    if mem is not None:
        device["memory_peak_bytes"] = mem

    # ---- the traced answer (--trace 1) -------------------------------------
    breakdown = None
    if args.trace:
        import reduce_trace

        tdir = os.path.join(harness.ROOT, ".bench_trace")
        shutil.rmtree(tdir, ignore_errors=True)
        tspans = harness.Spans()
        traced = reduce_trace.traced_answer(
            tdir, lambda: kind.answer(cfg, mix, runs[-1], tspans))
        shutil.rmtree(tdir, ignore_errors=True)
        device["busy_s"] = traced["busy_s"]
        device["window_s"] = traced["window_s"]
        breakdown = traced["breakdown"]
        ctx = {"spans": spans.durations, "answers": win.results,
               "trace": traced, "config": cfg, "traffic": mix,
               "device_kind": device["kind"]}
        metrics = {}
        for m in harness.cell_metrics(spec, args.workload, "per_layer"):
            value = harness.load_module("metrics", m["name"]).read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        # an answer kind may report end-to-end metrics of its own
        e2e = dict(getattr(kind, "end_to_end", lambda results: {})(
            win.results))
        # the answer time, under the name the traffic mix gives it, if any
        e2e[mix.get("time_metric", kind.TIME_METRIC)] = win.per_answer_s
        e2e["setup_s"] = setup_s
        metrics = {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                   for m in harness.cell_metrics(spec, args.workload,
                                                 "end_to_end")}

    # ---- the check: the window's last answer against the references --------
    checks = kind.check(cfg, mix, runs[-1], win.results[-1],
                        limits or harness.load_limits(args.workload))
    correct = harness.checks_ok(checks)
    harness.print_checks(checks)
    # an answer that raises ends the run, so none in the window failed
    line = harness.result_line(correct, len(win.results), 0,
                               metrics, device, checks, breakdown)
    print(line, flush=True)
    return json.loads(line)


if __name__ == "__main__":
    main()
