#!/usr/bin/env python3
"""Where a cell's answer time goes, read from the program's own spans.

    python3 benchmarks/chip/program_spans.py --workload <cell> --seed <n> \
        --pairs 3

For one cell of `BENCHMARK.json`: the set-up `run.py` makes (the run's
deployments, one whole answer of each), then `--pairs` pairs of answers,
one with the default `repro.obs.NullRecorder` and one under a
`repro.obs.Recorder` (order alternating from pair to pair), then one more
answer under the JAX profiler with the recorder installed.  Prints one
JSON line:

* `off_s`, `on_s`, `overhead`: seconds per answer without and with the
  recorder, and the median of the pairs' ratios (what tracing costs);
* `spans`: per answer with the recorder, the seconds of each program span
  (`total_s`, and `self_s` without the spans inside it) and of each
  harness span (`harness.Spans`, the layers as `run.py` times them);
* `counters`: per answer, each program counter, split by its `fn` label;
* `window_cache_hits`: per answer, compiled programs loaded again from
  the persistent cache, by program name; `compile_s`: per answer, the
  seconds of JAX's trace, lowering and compile events;
* `scopes`: device seconds under each program scope in the profiled
  answer (null where the trace has no device ops, as on a CPU); an op
  that fuses work of two scopes counts under both;
* `idle_gaps`: the profiled answer's device idle time, each stretch named
  by the innermost span open, program spans included; `layer_idle_gaps`
  the same without the `blockwise.*` spans, so that a sweep's idle time
  stays with the layer that runs it;
* `readings`: `fw_loads_ms` (device ms under `fluid.loads` per FW step),
  `packet_arbitrate_ms` (under `packet.arbitrate` per cycle),
  `paths_sweep_s`, `paths_walk_s` (per answer) and `routing_retraces`
  (`blockwise.retrace` per answer); null where the cell runs no such
  code.
"""

from __future__ import annotations

import argparse
import glob
import json
import logging
import os
import shutil
import statistics
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import harness  # noqa: E402

# the program's device scopes (`jax.named_scope`), each read apart
SCOPES = ("fluid.loads", "fluid.cost", "fluid.target", "fluid.line_search",
          "fluid.certify", "minplus.path_costs", "packet.route",
          "packet.arbitrate", "packet.queues")


def per_answer(events: list, answers: int) -> tuple:
    """(spans, counters) of the recorded answers, per answer: each span
    name's total and self seconds, each counter's sum by its `fn`."""
    from repro.obs.record import summarize_spans

    spans = {name: {"total_s": row["total_us"] * 1e-6 / answers,
                    "self_s": row["self_us"] * 1e-6 / answers}
             for name, row in summarize_spans(events).items()}
    counters: dict = {}
    for ev in events:
        if ev["ph"] == "C" and not ev["args"].get("gauge"):
            key = ev["name"] + (f"[{ev['args']['fn']}]"
                                if "fn" in ev["args"] else "")
            counters[key] = counters.get(key, 0.0) + \
                ev["args"]["value"] / answers
    return spans, counters


class CacheHits(logging.Handler):
    """Counts JAX's persistent-cache hits by program name, from the
    compiler's debug log (`CompileMonitor` counts them without names)."""

    def __init__(self):
        super().__init__(logging.DEBUG)
        self.names: dict = {}
        self.log = logging.getLogger("jax._src.compiler")

    def emit(self, record):
        msg = record.getMessage()
        if msg.startswith("Persistent compilation cache hit for '"):
            name = msg.split("'")[1]
            self.names[name] = self.names.get(name, 0) + 1

    def __enter__(self):
        self.saved = (self.log.level, self.log.propagate)
        self.log.setLevel(logging.DEBUG)
        self.log.propagate = False  # no debug lines on standard error
        self.log.addHandler(self)
        return self

    def __exit__(self, *exc):
        self.log.removeHandler(self)
        self.log.setLevel(self.saved[0])  # also clears logging's level cache
        self.log.propagate = self.saved[1]


def readings(spans: dict, counters: dict, scopes: dict, traced: dict,
             mix: dict) -> dict:
    """The five quantities the program's spans give a cell; None where
    the cell runs no such code (or, for device time, where the trace has
    no device ops)."""
    def total(name):
        return spans[name]["total_s"] if name in spans else None

    def device_ms(scope, per):
        secs = scopes.get(scope)
        return secs * 1e3 / per if secs and per else None

    retraces = sum(v for k, v in counters.items()
                   if k.startswith("blockwise.retrace"))
    return {"fw_loads_ms": device_ms("fluid.loads", traced.get("iters")),
            "packet_arbitrate_ms": device_ms(
                "packet.arbitrate", mix["params"].get("cycles")
                if mix["answer"] == "tail" else None),
            "paths_sweep_s": total("paths.sweep"),
            "paths_walk_s": total("paths.walk"),
            "routing_retraces": retraces}


def profiled(kind, cfg: dict, mix: dict, inputs: dict, names) -> tuple:
    """One answer under the JAX profiler with a Recorder installed:
    (its result, the trace reduced with the program's scopes and with
    the idle time named by `names` as well as the harness spans, the idle
    time named without the `blockwise.*` spans)."""
    from repro.obs import Recorder, recording

    import reduce_trace

    tdir = os.path.join(harness.ROOT, ".bench_trace")
    shutil.rmtree(tdir, ignore_errors=True)
    got = []
    # the reduction keeps and names idle time by the spans in its SPANS:
    # give it the program's too, in this process only
    base = tuple(reduce_trace.SPANS)
    try:
        reduce_trace.SPANS = base + tuple(names)
        with recording(Recorder()):
            trace = reduce_trace.traced_answer(
                tdir, lambda: got.append(kind.answer(cfg, mix, inputs,
                                                     harness.Spans())),
                {s: "" for s in SCOPES})
        reduce_trace.SPANS = base + tuple(
            n for n in names if not n.startswith("blockwise."))
        (path,) = glob.glob(os.path.join(tdir, "**", "*.xplane.pb"),
                            recursive=True)
        layer_idle = reduce_trace.reduce(
            reduce_trace.extract(path))["breakdown"]["idle_gaps"]
    finally:
        reduce_trace.SPANS = base
        shutil.rmtree(tdir, ignore_errors=True)
    return got[0], trace, layer_idle


def main(argv=None, require_tpu: bool = True, config: dict = None,
         traffic: dict = None) -> dict:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--pairs", type=int, default=3)
    args = ap.parse_args(argv)
    spec = harness.benchmark_spec()
    cell = harness.workload_entry(spec, args.workload)
    cfg = config or harness.load_config(cell["config"])
    mix = traffic or harness.load_traffic(cell["traffic"])
    kind = harness.load_module("answers", mix["answer"])
    if require_tpu:
        harness.use_compile_cache()
        harness.require_chips(cell["chips"])
    mon = harness.CompileMonitor()
    harness.add_program()
    from repro.obs import Recorder, recording

    runs = harness.run_inputs(cfg["N"], mix, args.seed)
    for inputs in runs:
        kind.answer(cfg, mix, inputs, harness.Spans())

    # whole answers, timed by harness spans: "off" with the NullRecorder,
    # "on" with the Recorder; layer spans of the "on" answers in `spans`
    rec, timed, spans, hits = (Recorder(), harness.Spans(), harness.Spans(),
                               CacheHits())
    compile0 = mon.compile_s
    with hits:
        for i in range(args.pairs):
            inputs = runs[i % len(runs)]  # both answers of a pair alike
            for side in (("off", "on") if i % 2 == 0 else ("on", "off")):
                if side == "on":
                    rec.request(i)
                    with recording(rec), timed("on"):
                        kind.answer(cfg, mix, inputs, spans)
                else:
                    with timed("off"):
                        kind.answer(cfg, mix, inputs, harness.Spans())
    rec.request(None)
    off, on = timed.durations["off"], timed.durations["on"]
    n = len(off) + len(on)
    span_s, counters = per_answer(rec.events(), len(on))
    for name, ds in spans.durations.items():
        span_s[f"harness:{name}"] = {"total_s": sum(ds) / len(on)}

    got, trace, layer_idle = profiled(
        kind, cfg, mix, runs[-1],
        [k for k in span_s if not k.startswith("harness:")])
    scopes = {s: (trace["scopes"][s]["seconds"] if trace["busy_s"] > 0
                  else None) for s in SCOPES}
    out = {"workload": args.workload, "seed": args.seed,
           "off_s": off, "on_s": on,
           "overhead": statistics.median(b / a for a, b in zip(off, on)),
           "spans": span_s, "counters": counters,
           "window_cache_hits": {k: v / n for k, v in hits.names.items()},
           "compile_s": (mon.compile_s - compile0) / n,
           "scopes": scopes, "busy_s": trace["busy_s"],
           "window_s": trace["window_s"],
           "idle_gaps": trace["breakdown"]["idle_gaps"],
           "layer_idle_gaps": layer_idle,
           "readings": readings(span_s, counters, scopes, got, mix)}
    print(json.dumps(out), flush=True)
    return out


if __name__ == "__main__":
    main()
