"""Core of the chip benchmark: finds a cell's pieces by name, times its
answers, reads its per-layer metrics and prints the result line.

Everything that belongs to one configuration, traffic mix, answer kind or
per-layer metric lives in a file of its own under this directory, found by
the name `BENCHMARK.json` gives it:

    configs/<config>.json    one deployment (sizes, routing mode, source)
    traffic/<traffic>.json   one traffic mix (pattern, answer kind, params;
                             `time_metric`: the name its answer time is
                             reported under, where not the answer kind's)
    limits/<workload>.json   the limit of each number a cell's check compares
    answers/<kind>.py        one answer kind: builds and answers, checks
    metrics/<metric>.py      one per-layer metric: `read(ctx)` -> value
    peaks.json               device peaks keyed by `device_kind`

This module imports nothing of the simulator, so the yardstick (timing,
traffic generation, tails, trace reduction, peaks) stays fixed when the
program under test changes.
"""

from __future__ import annotations

import contextlib
import importlib.util
import json
import os
import statistics
import sys
import time
from typing import Callable, Dict, List, Optional, Sequence

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


# --------------------------------------------------------------------------
# the benchmark's own files, found by name
# --------------------------------------------------------------------------

def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark_spec() -> dict:
    return load_json(os.path.join(ROOT, "BENCHMARK.json"))


def workload_entry(spec: dict, name: str) -> dict:
    for w in spec["workloads"]:
        if w["name"] == name:
            return w
    raise SystemExit(f"unknown workload {name!r}; BENCHMARK.json has "
                     f"{[w['name'] for w in spec['workloads']]}")


def load_config(name: str) -> dict:
    return load_json(os.path.join(HERE, "configs", f"{name}.json"))


def load_traffic(name: str) -> dict:
    return load_json(os.path.join(HERE, "traffic", f"{name}.json"))


def load_limits(workload: str) -> dict:
    """The limits of the numbers a cell's check compares."""
    return load_json(os.path.join(HERE, "limits", f"{workload}.json"))


def load_module(kind: str, name: str):
    """Import ``<kind>/<name>.py`` under a private module name (the
    file name may hold dots, e.g. ``metrics/device_idle.sat.py``)."""
    path = os.path.join(HERE, kind, f"{name}.py")
    if not os.path.isfile(path):
        raise FileNotFoundError(f"no {kind} module {path}")
    spec = importlib.util.spec_from_file_location(
        f"chipbench_{kind}_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def cell_metrics(spec: dict, cell: str, section: str) -> List[dict]:
    """The `end_to_end` or `per_layer` entries that `cell` reports."""
    return [m for m in spec[section]
            if "workloads" not in m or cell in m["workloads"]]


def load_peaks(device_kind: str) -> dict:
    """Peaks of one device kind; a kind missing from the table is an
    error, never a default."""
    table = load_json(os.path.join(HERE, "peaks.json"))
    if device_kind not in table["devices"]:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"peaks.json (has {sorted(table['devices'])})")
    return table["devices"][device_kind]


# --------------------------------------------------------------------------
# inputs drawn from the seed
# --------------------------------------------------------------------------

def derangement(n: int, rng: np.random.Generator) -> np.ndarray:
    """A uniformly random permutation of range(n) with no fixed point
    (rejection sampling: about e draws on average)."""
    if n < 2:
        raise ValueError("a derangement needs n >= 2")
    ids = np.arange(n)
    while True:
        perm = rng.permutation(n)
        if not (perm == ids).any():
            return perm


def draw_inputs(n: int, seed: int) -> dict:
    """Everything a run draws from `--seed`: the destination of each
    router's flow (a derangement, so every one of the n routers sends:
    F = n for every seed) and the seed of the generator the program draws
    its Valiant intermediates and packet phases from."""
    rng = np.random.default_rng(seed)
    dst = derangement(n, rng)
    return {"src": np.arange(n, dtype=np.int32),
            "dst": dst.astype(np.int32),
            "program_seed": int(rng.integers(2 ** 62))}


def run_inputs(n: int, mix: dict, seed: int) -> List[dict]:
    """The deployments a run answers, in the order it answers them.

    Without a `pool` in the traffic mix, one deployment drawn from the
    seed.  With `"pool": {"seed": s, "size": k}`, the same k deployments
    for every run (drawn from s), in an order drawn from the run's seed:
    a mix whose work varies from draw to draw (the program compiles for
    shapes of the draw, and its solve's step count follows the draw) gives
    every seed the same work that way."""
    pool = mix.get("pool")
    if pool is None:
        return [draw_inputs(n, seed)]
    seeds = np.random.SeedSequence(int(pool["seed"])).spawn(int(pool["size"]))
    insts = [draw_inputs(n, s) for s in seeds]
    return [insts[i] for i in np.random.default_rng(seed).permutation(
        len(insts))]


# --------------------------------------------------------------------------
# nearest-rank tails (the yardstick's own copy)
# --------------------------------------------------------------------------

def tail_percentiles(latencies: np.ndarray,
                     qs: Sequence[float] = (0.5, 0.99, 0.999)
                     ) -> Dict[str, int]:
    """Nearest-rank percentiles of an integer sample: the ceil(q * n)-th
    smallest value (no interpolation).  Keys p50/p99/p999."""
    lat = np.sort(np.asarray(latencies))
    if not len(lat):
        raise ValueError("no samples to take percentiles of")
    out = {}
    for q in qs:
        idx = max(0, int(np.ceil(q * len(lat))) - 1)
        out[f"p{q * 100:g}".replace(".", "")] = int(lat[idx])
    return out


# --------------------------------------------------------------------------
# JAX: compile cache, compile counter, device
# --------------------------------------------------------------------------

def use_compile_cache(checkout: str = ROOT) -> str:
    """JAX's persistent compilation cache at ``<checkout>/.jax_cache``, a
    fixed path inside the checkout (the path is part of the cache key, and
    a cache outside the checkout could be shared between two checkouts).
    Every program is cached, however fast it compiled."""
    import jax

    path = os.path.join(os.path.abspath(checkout), ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


_COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                   "/jax/core/compile/jaxpr_to_mlir_module_duration",
                   "/jax/core/compile/backend_compile_duration")


class CompileMonitor:
    """Compile seconds, backend compiles and persistent-cache hits/misses,
    from JAX's own monitoring events.  One per process: JAX keeps its
    listeners for the life of the process."""

    def __init__(self):
        import jax

        self.compile_s = 0.0
        self.compiles = 0
        self.hits = 0
        self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_kw):
        if event in _COMPILE_EVENTS:
            self.compile_s += duration
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1

    def _event(self, event, **_kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1


def add_program() -> None:
    """Put the program under test (``<checkout>/src``) on the import
    path; a checkout without it is an error, before any result."""
    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        raise SystemExit(f"no program under test at {src}/repro; "
                         f"nothing was run")
    if src not in sys.path:
        sys.path.insert(0, src)


def require_chips(chips: int) -> dict:
    """The device record of the result line; exits non-zero, before any
    result, where JAX finds no TPU or fewer chips than the cell needs."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"no TPU: JAX's default device is "
                         f"{devs[0].platform}; nothing was run")
    if len(devs) < chips:
        raise SystemExit(f"the cell needs {chips} chips, JAX finds "
                         f"{len(devs)}")
    return device_record(chips)


def device_record(chips: int) -> dict:
    import jax

    devs = jax.devices()[:chips]
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak_bytes(chips: int) -> Optional[int]:
    import jax

    peaks = []
    for d in jax.devices()[:chips]:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


# --------------------------------------------------------------------------
# spans
# --------------------------------------------------------------------------

class Spans:
    """Host-clock spans the answers open around each layer they call.
    Each span is also a `jax.profiler.TraceAnnotation`, so a profiler
    trace names the host's activity during every device idle gap."""

    def __init__(self):
        self.durations: Dict[str, List[float]] = {}

    @contextlib.contextmanager
    def __call__(self, name: str):  # reprolint: allow[naked-clock] -- host span of a layer call that blocks on its device results before returning
        import jax

        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(name):
            yield
        self.durations.setdefault(name, []).append(time.perf_counter() - t0)


# --------------------------------------------------------------------------
# the run
# --------------------------------------------------------------------------

class Window:
    """Answers back to back for `seconds`, in whole passes over a run's
    `passes_of` deployments; a pass started inside the window runs to its
    end.  `per_answer_s` is the span from the first answer's start to the
    last one's end over the number of answers."""

    def __init__(self, seconds: float, passes_of: int = 1):
        self.seconds = float(seconds)
        self.passes_of = int(passes_of)
        self.results: List[dict] = []
        self.elapsed = 0.0

    def run(self, one: Callable[[int], dict]) -> None:  # reprolint: allow[naked-clock] -- each answer blocks on its results before returning
        """`one(i)` gives the i-th answer."""
        t0 = time.perf_counter()
        while (not self.results or len(self.results) % self.passes_of
               or time.perf_counter() - t0 < self.seconds):
            self.results.append(one(len(self.results)))
        self.elapsed = time.perf_counter() - t0

    @property
    def per_answer_s(self) -> float:
        return self.elapsed / len(self.results)


def spread(values: Sequence[float]) -> float:
    """Quartile spread over the median (Python's `statistics.quantiles`)."""
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def checks_ok(checks: Dict[str, dict]) -> bool:
    return all(c["value"] <= c["limit"] for c in checks.values())


def print_checks(checks: Dict[str, dict]) -> None:
    """Each compared number beside its limit, as the last lines on
    standard error."""
    for name, c in checks.items():
        verdict = "ok" if c["value"] <= c["limit"] else "FAILED"
        print(f"check {name} = {c['value']!r} limit {c['limit']!r} {verdict}",
              file=sys.stderr, flush=True)


def result_line(correct: bool, attempted: int, failed: int, metrics: dict,
                device: dict, checks: dict,
                breakdown: Optional[dict] = None) -> str:
    out = {"correct": bool(correct), "attempted": int(attempted),
           "failed": int(failed), "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = checks
    return json.dumps(out)
