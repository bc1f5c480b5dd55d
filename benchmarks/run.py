"""Run every benchmark; one per paper table/figure + kernels/fabric/roofline.

Prints `name,us_per_call,derived` CSV and writes a machine-readable
`BENCH_<TIER>.json` (TIER in SMOKE/FULL/LARGE, from BENCH_SMOKE /
BENCH_LARGE) next to the repo root -- or under $BENCH_JSON_DIR when set.
The JSON carries per-figure wall times, every emitted row, and the
measured saturation points extracted from `sat=` derived values, so runs
can be diffed across commits without re-parsing stdout.

When `benchmarks/baselines/BENCH_<TIER>.json` exists (the SMOKE and FULL
baselines are committed), the run is diffed against it: any figure whose
wall time regressed more than 25% prints a `# WARN` line.  LARGE runs,
which have no baseline of their own, additionally diff individual rows
against the FULL baseline by name.  Warnings never fail the run -- wall
times on shared CI runners are noisy -- but they make a regression
visible in the job log the moment it lands.

Each figure also runs under a fresh `repro.obs.Recorder`: the
instrumented library paths emit spans/counters into it, a Chrome-trace
JSONL per figure lands under `<out_dir>/bench_traces/`, and the
aggregated summaries go into the report's `obs` table.
"""
import importlib
import json
import os
import sys
import time
import traceback

from benchmarks import common
from repro.obs import Recorder, recording

BENCHES = [
    "bench_fig1_feasible_degrees",
    "bench_fig2_moore",
    "bench_table2_triangles",
    "bench_table6_diversity",
    "bench_paths_engine",
    "bench_fluid_engine",
    "bench_fig8_saturation",
    "bench_fig9_adaptive",
    "bench_fig10_sizes",
    "bench_fig11_expansion",
    "bench_fig12_bisection",
    "bench_fig14_resilience",
    "bench_fig_tail",
    "bench_fig15_cost",
    "bench_fabric",
    "bench_kernels",
    "bench_roofline",
    "bench_blockwise_scaling",
]

# Committed reference timings (per tier) the current run is diffed against.
BASELINE_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            "baselines")
REGRESSION_RATIO = 1.25


def _kv(derived: str) -> dict:
    """Parse a `k=v;k=v` derived string (rows may carry several fields)."""
    out = {}
    for part in derived.split(";"):
        if "=" in part:
            k, v = part.split("=", 1)
            out[k] = v
    return out


def _floats(kv: dict, keys) -> dict:
    out = {}
    for k in keys:
        if k in kv:
            try:
                out[k] = float(kv[k].rstrip("x"))
            except ValueError:
                pass
    return out


def _saturations(rows) -> dict:
    """{row name: float} for every row carrying a `sat=<x>` field."""
    out = {}
    for row in rows:
        got = _floats(_kv(row["derived"]), ("sat",))
        if "sat" in got:
            out[row["name"]] = got["sat"]
    return out


def _certifications(rows) -> dict:
    """Certified-solver rows (those carrying a `gap=` field): the duality
    gap, certified saturation bracket, iteration count, and accuracy vs
    the reference engine, parsed out of the derived string so certified
    tolerances can be diffed across commits like the saturations."""
    out = {}
    for row in rows:
        kv = _kv(row["derived"])
        if "gap" in kv:
            out[row["name"]] = _floats(
                kv, ("sat", "gap", "lo", "hi", "iters", "err_vs_ref",
                     "speedup"))
    return out


def _tails(rows) -> dict:
    """Packet-engine tail rows (those carrying a `p99=` field): the
    latency percentiles plus delivery/drop counts, so tail regressions
    diff across commits like the saturations do."""
    out = {}
    for row in rows:
        kv = _kv(row["derived"])
        if "p99" in kv:
            out[row["name"]] = _floats(
                kv, ("p50", "p99", "p999", "delivered", "dropped", "P"))
    return out


def _truncations(rows) -> dict:
    """{row name: float} for rows carrying a `trunc=<x>` field (the
    adaptive-mode Frank-Wolfe truncation-error estimate at the reported
    saturation)."""
    out = {}
    for row in rows:
        got = _floats(_kv(row["derived"]), ("trunc",))
        if "trunc" in got:
            out[row["name"]] = got["trunc"]
    return out


# Row-level diffs (LARGE vs the committed FULL baseline) skip rows whose
# baseline cost is below this floor: sub-millisecond rows are dominated by
# dispatch noise and would WARN spuriously at any ratio.
ROW_FLOOR_US = 1000.0


def diff_rows_against_full(figures: dict,
                           baseline_dir: str = BASELINE_DIR) -> list:
    """`# WARN` lines for individual rows whose us_per_call regressed more
    than `REGRESSION_RATIO` against the committed FULL baseline.

    LARGE runs have no committed baseline of their own (they are too slow
    to regenerate on every commit), but most of their rows -- everything
    except the extra large-scale points -- are the same measurements the
    FULL tier makes, so those are diffed row-by-row against
    `baselines/BENCH_FULL.json`.  Rows only the LARGE tier emits have no
    baseline entry and are skipped, as are rows under `ROW_FLOOR_US`.
    """
    path = os.path.join(baseline_dir, "BENCH_FULL.json")
    if not os.path.exists(path):
        return []
    with open(path, encoding="utf-8") as fh:
        base = json.load(fh).get("figures", {})
    base_rows = {r["name"]: r["us_per_call"]
                 for fig in base.values() for r in fig.get("rows", [])}
    warns = []
    for name in sorted(figures):
        for row in figures[name]["rows"]:
            ref = base_rows.get(row["name"], 0.0)
            if ref >= ROW_FLOOR_US and \
                    row["us_per_call"] > REGRESSION_RATIO * ref:
                warns.append(
                    f"# WARN {row['name']}: {row['us_per_call']:.1f}us vs "
                    f"FULL baseline {ref:.1f}us "
                    f"({row['us_per_call'] / ref:.2f}x > "
                    f"{REGRESSION_RATIO:.2f}x)")
    return warns


def diff_against_baseline(figures: dict, tier: str,
                          baseline_dir: str = BASELINE_DIR) -> list:
    """`# WARN` lines for figures whose wall time regressed more than
    `REGRESSION_RATIO` against the committed `BENCH_<tier>.json` baseline.
    No baseline file (or no baseline entry for a figure -- new benches) is
    not a warning: there is nothing to regress against.
    """
    path = os.path.join(baseline_dir, f"BENCH_{tier}.json")
    if not os.path.exists(path):
        return []
    with open(path, encoding="utf-8") as fh:
        base = json.load(fh).get("figures", {})
    warns = []
    for name in sorted(figures):
        wall = figures[name]["wall_s"]
        ref = base.get(name, {}).get("wall_s", 0)
        if ref > 0 and wall > REGRESSION_RATIO * ref:
            warns.append(f"# WARN {name}: wall {wall:.3f}s vs baseline "
                         f"{ref:.3f}s ({wall / ref:.2f}x > "
                         f"{REGRESSION_RATIO:.2f}x)")
    return warns


def write_report(figures: dict, path: str, obs: dict = None) -> None:
    rows = [r for fig in figures.values() for r in fig["rows"]]
    report = {
        "tier": common.tier(),
        "total_wall_s": round(sum(f["wall_s"] for f in figures.values()), 3),
        "figures": figures,
        "saturations": _saturations(rows),
        "certifications": _certifications(rows),
        "truncation_err": _truncations(rows),
        "tails": _tails(rows),
    }
    if obs is not None:
        # per-figure Recorder summaries (span totals, counters, gauges)
        # from the instrumented solver/executor/packet paths
        report["obs"] = obs
    with open(path, "w", encoding="utf-8") as fh:
        json.dump(report, fh, indent=2, sort_keys=True)
        fh.write("\n")
    print(f"# wrote {path}", flush=True)


def main() -> None:  # reprolint: allow[naked-clock] -- times whole bench modules (imports + device work each bench already blocks on), not individual device calls; common.timed is for those
    common.use_compile_cache(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    print("name,us_per_call,derived")
    failures = 0
    only = sys.argv[1:] or None
    figures = {}
    obs = {}
    out_dir = os.environ.get("BENCH_JSON_DIR", ".")
    traces_dir = os.path.join(out_dir, "bench_traces")
    os.makedirs(traces_dir, exist_ok=True)
    for mod in BENCHES:
        if only and not any(o in mod for o in only):
            continue
        rec = Recorder()
        t0 = time.perf_counter()
        try:
            # a fresh Recorder per figure: the instrumented library paths
            # (fluid solver spans, blockwise per-block spans, packet
            # occupancy metrics) report into it for the module's duration
            with recording(rec):
                with rec.span("bench.figure", figure=mod):
                    importlib.import_module(f"benchmarks.{mod}").run()
        except Exception:  # noqa: BLE001
            failures += 1
            print(f"{mod},0,ERROR", flush=True)
            traceback.print_exc()
            common.drain_rows()  # don't attribute the partial rows
            continue
        figures[mod] = {"wall_s": round(time.perf_counter() - t0, 3),
                        "rows": common.drain_rows()}
        rec.dump(os.path.join(traces_dir, f"{mod}.trace.jsonl"))
        obs[mod] = rec.summary()
    write_report(figures, os.path.join(out_dir,
                                       f"BENCH_{common.tier()}.json"),
                 obs=obs)
    print(f"# traces under {traces_dir}", flush=True)
    for warn in diff_against_baseline(figures, common.tier()):
        print(warn, flush=True)
    if common.tier() == "LARGE":
        for warn in diff_rows_against_full(figures):
            print(warn, flush=True)
    if failures:
        raise SystemExit(f"{failures} benchmarks failed")


if __name__ == "__main__":
    main()
