"""Shared benchmark harness: timing + CSV emission.

Every bench prints `name,us_per_call,derived` rows; `derived` carries the
paper-relevant quantity (saturation, fraction, count, ...).
"""

from __future__ import annotations

import os
import time
from typing import Callable


def use_compile_cache(checkout: str) -> str:
    """Point JAX's persistent compilation cache at ``<checkout>/.jax_cache``
    and return the directory in use.  Where ``JAX_COMPILATION_CACHE_DIR``
    is set, JAX reads it itself and nothing is set here.  Entry points
    (`benchmarks.run`, `chip_smoke.py`) call this before their first
    compile; the library never does, since tests compile for described
    chips whose executables cannot be read back from a cache."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    path = os.path.join(os.path.abspath(checkout), ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def timed(fn: Callable, repeats: int = 1):
    """Wall-clock `fn`, synchronizing device outputs before reading the
    clock: JAX dispatches asynchronously, so without blocking on the result
    the timer can stop while device work is still in flight.  Non-array
    outputs pass through `jax.block_until_ready` untouched.  (jax is
    imported lazily so the pure-numpy benches skip the import cost.)"""
    import jax

    t0 = time.perf_counter()
    out = None
    for _ in range(repeats):
        out = jax.block_until_ready(fn())
    dt = (time.perf_counter() - t0) / repeats
    return out, dt * 1e6


# Adaptive (UGAL / UGAL_PF) saturations need convergence-grade Frank-Wolfe
# budgets -- see the truncation-noise discussion in repro/simulation/fluid.py;
# oblivious splits are load-independent, so the solver default suffices.
ADAPTIVE_ITERS = 1500


def fw_iters(mode: str) -> int:
    """Frank-Wolfe iteration budget for a routing mode's saturation solve."""
    return ADAPTIVE_ITERS if mode in ("ugal", "ugal_pf") else 250


def smoke() -> bool:
    """True when BENCH_SMOKE=1: benchmarks shrink to PF(7)-scale configs so
    CI can smoke-test every figure in minutes."""
    return os.environ.get("BENCH_SMOKE", "0") not in ("", "0")


def large() -> bool:
    """True when BENCH_LARGE=1: figure benchmarks add the 5k-25k-endpoint
    scale tier (PS(9,61) / SF(43) / PF(79) / matched-radix Jellyfish) that
    is only feasible with the sparse blocked-BFS graph engine."""
    return os.environ.get("BENCH_LARGE", "0") not in ("", "0")


def tier() -> str:
    """Active tier name (stamps the BENCH_<TIER>.json the runner writes)."""
    if large():
        return "LARGE"
    if smoke():
        return "SMOKE"
    return "FULL"


# Rows emitted since the last `drain_rows()` call; `benchmarks.run` drains
# after each bench module to build the per-figure JSON record.
_ROWS: list = []


def drain_rows() -> list:
    rows, _ROWS[:] = _ROWS[:], []
    return rows


def emit(name: str, us: float, derived) -> None:
    _ROWS.append({"name": name, "us_per_call": round(us, 1),
                  "derived": str(derived)})
    print(f"{name},{us:.1f},{derived}", flush=True)
