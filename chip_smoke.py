#!/usr/bin/env python3
"""Bring-up smoke run of the simulator's main path on TPU chips.

Run from the repository root, on a machine with TPU chips:

    python chip_smoke.py             # one chip: PF(79) fluid + packet path
    python chip_smoke.py --chips 4   # four chips: sharded PF(79) routing build

One chip drives the public API at the PolarFly PF(79) scale tier (6,321
routers, radix 80, 505,600 directed links) in one process:

1. packet engine vs its reference on a PF(13) workload (equal outcomes);
2. PF(79) build: `build_polarfly` -> `build_blocked_routing` (its BFS
   blocks on the chip, through the blockwise executor's device backend) ->
   `make_pattern("random_perm")` -> `build_flow_paths(..., "ugal")`;
   sampled destination columns are checked bit for bit against the host
   backend;
3. `path_costs` on the PF(79) edge ids against `path_costs_ref`, both on
   the chip;
4. certified UGAL saturation inside its own certified bracket, and within
   2*tol + 0.02 of the uncertified batched engine;
5. packet engine on the same paths: packets conserved, p50/p99/p999.

`--chips 4` runs only the sharded blockwise routing build on four chips
and compares it bit for bit with the host backend on sampled destination
columns: all 6,321 take minutes of numpy on the host backend, and
tests/test_blockwise.py compares every column on smaller graphs.

Every check that fails ends the run with a non-zero exit.  So does a run
that finds no TPU.  The last line of standard output is one JSON object,
``{"ok": true, "device": {"platform", "kind", "count"}}``, printed only
when every phase passed.  JAX's persistent compilation cache lives in
``JAX_COMPILATION_CACHE_DIR`` when set, else in ``<checkout>/.jax_cache``.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from benchmarks.common import use_compile_cache  # noqa: E402
from repro.core.polarfly import build_polarfly  # noqa: E402
from repro.core.routing import build_blocked_routing, build_routing  # noqa: E402
from repro.kernels.minplus.ops import path_costs  # noqa: E402
from repro.kernels.minplus.ref import path_costs_ref  # noqa: E402
from repro.obs import Recorder, recording  # noqa: E402
from repro.simulation import (build_flow_paths, make_pattern,  # noqa: E402
                              make_workload, saturation_throughput,
                              simulate_packets, simulate_packets_reference)

TOL = 0.01            # bisection tolerance of both saturation engines
CERT_ITERS = 1000     # certified engine's per-probe iteration cap
BATCH_ITERS = 4000    # uncertified engine's budget (bench_fluid_engine bar)
PACKET_LOAD = 0.3
PACKET_CYCLES = 400
SAMPLE_BLOCKS = 16    # destination blocks checked against the host backend

_COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                   "/jax/core/compile/jaxpr_to_mlir_module_duration",
                   "/jax/core/compile/backend_compile_duration")


class _Jax:
    """Compile seconds and persistent-cache hits/misses, from JAX's own
    monitoring events."""

    def __init__(self):
        self.compile_s = 0.0
        self.hits = 0
        self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_kw):
        if event in _COMPILE_EVENTS:
            self.compile_s += duration

    def _event(self, event, **_kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1


class _Phase:
    """Times one phase (wall clock, and the compile seconds inside it)."""

    def __init__(self, name: str, mon: _Jax):
        self.name, self.mon = name, mon

    def __enter__(self):
        self.c0 = self.mon.compile_s
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        self.wall = time.perf_counter() - self.t0
        self.compile = self.mon.compile_s - self.c0
        if exc[0] is None:
            print(f"phase {self.name}: wall_s={self.wall:.3f} "
                  f"compile_s={self.compile:.3f}", flush=True)
        return False


def check(ok: bool, what: str) -> None:
    print(f"check {what}: {'ok' if ok else 'FAILED'}", flush=True)
    if not ok:
        raise SystemExit(f"chip_smoke: check failed: {what}")


def packet_reference_pf13(mon: _Jax) -> None:
    """The scan engine on the chip against the Python reference engine:
    equal per-packet outcomes on a PF(13) UGAL workload."""
    with _Phase("pf13_packet_vs_reference", mon):
        pf = build_polarfly(13)
        rt = build_routing(pf.graph, pf)
        pat = make_pattern("uniform", rt, p=7, seed=0)
        fp = build_flow_paths(rt, pat, "ugal", k_candidates=8, seed=0)
        wl = make_workload(fp, 0.4, 200, seed=1)
        got = simulate_packets(wl)
        ref = simulate_packets_reference(wl)
    print(f"pf13 packets={wl.num_packets} delivered={got.num_delivered} "
          f"tails={got.tails()}", flush=True)
    check(np.array_equal(got.delivered, ref.delivered)
          and np.array_equal(got.dropped, ref.dropped)
          and np.array_equal(got.deliver_t[got.delivered],
                             ref.deliver_t[ref.delivered])
          and got.admitted == ref.admitted,
          "pf13 packet engine outcomes equal the reference")
    check(np.array_equal(got.latencies(), ref.latencies()),
          "pf13 packet latencies equal the reference")


def routing_matches_host(rt, mon: _Jax, what: str) -> set:
    """Destination columns of `rt` (device backend) against the host
    backend's, bit for bit, on SAMPLE_BLOCKS seeded random destination
    blocks; returns the ids of the devices that computed them."""
    g = rt.graph
    dests = np.sort(np.random.default_rng(0).choice(
        g.n, size=min(g.n, SAMPLE_BLOCKS * rt.block), replace=False))
    host = dataclasses.replace(rt, backend="host", devices=None)
    rec = Recorder()
    same = cols = diam = 0
    with _Phase(f"{what}_sampled_columns_vs_host", mon):
        with recording(rec):
            dev_cols = list(rt.dest_blocks(dests))
        for (ds, dist_s, nh_s), (dh, dist_h, nh_h) in zip(
                dev_cols, host.dest_blocks(dests)):
            cols += len(ds)
            same += int(np.array_equal(ds, dh)
                        and np.array_equal(dist_s, dist_h)
                        and np.array_equal(nh_s, nh_h)) * len(ds)
            diam = max(diam, int(dist_h.max()))
    check(cols == len(dests) and same == cols,
          f"{what}: {cols} sampled destination columns bit-identical to "
          f"the host backend ({same} equal)")
    # every PF(q) column reaches the ER_q diameter 2, so the sample's
    # largest host distance is the whole graph's diameter
    check(rt.diameter == diam,
          f"{what}: diameter {rt.diameter} equals the host backend's {diam}")
    return _block_devices(rec)


def _block_devices(rec: Recorder) -> set:
    return {e["args"]["device"] for e in rec.events()
            if e["ph"] == "X" and e["name"] == "blockwise.block"}


def build_pf79(mon: _Jax):
    """The main path's build at the shapes of bench_fluid_engine's PF(79)
    point.  Routing runs the blocked BFS on the chip (backend "sharded"
    over one device); the host backend would take minutes of numpy here."""
    with _Phase("pf79_graph", mon):
        g = build_polarfly(79).graph
    with _Phase("pf79_routing", mon):
        rt = build_blocked_routing(g, backend="sharded", devices=1)
    routing_matches_host(rt, mon, "pf79_routing")
    with _Phase("pf79_paths", mon):
        pat = make_pattern("random_perm", rt, p=g.params["radix"] // 2,
                           seed=0, max_flows=60_000)
        fp = build_flow_paths(rt, pat, "ugal", k_candidates=10, seed=0)
    with _Phase("pf79_device_arrays", mon):
        jax.block_until_ready(fp.device_arrays())
    print(f"pf79 routers={g.n} links={fp.num_links} "
          f"eidx={list(fp.edges.shape)} diameter={rt.diameter}", flush=True)
    return fp


def path_costs_on_chip(fp, mon: _Jax) -> None:
    """`path_costs` on the PF(79) edge ids against the per-hop reference,
    both on the chip.  Delays are the M/D/1 link costs at seeded random
    utilizations, with the zero pad slot last."""
    eidx = fp.device_arrays()[0]
    rho = np.random.default_rng(0).random(fp.num_links) * 0.999
    delay = np.append(1.0 + rho / (2.0 * (1.0 - rho)), 0.0)
    with _Phase("pf79_path_costs", mon):
        d = jnp.asarray(delay, jnp.float32)
        got = jax.block_until_ready(jax.jit(path_costs)(d, eidx))
        ref = jax.block_until_ready(jax.jit(path_costs_ref)(d, eidx))
    check({dv.platform for dv in got.devices() | ref.devices()} == {"tpu"},
          "path_costs and path_costs_ref ran on the TPU")
    got, ref = np.asarray(got), np.asarray(ref)
    exact = np.asarray(d, np.float64)[np.asarray(eidx)].sum(axis=-1)
    # each cost is a sum of L positive float32 delays; two summation
    # orders differ by at most (L - 1) roundings of the total, and either
    # one is within (L - 1) roundings of the exact sum
    rtol = (eidx.shape[-1] - 1) * 2.0 ** -24
    rel = float(np.max(np.abs(got - ref) / np.maximum(ref, 1e-30)))
    print(f"path_costs shape={list(got.shape)} "
          f"bit_identical={np.array_equal(got, ref)} "
          f"entries_differing={int((got != ref).sum())} "
          f"max_rel_diff={rel!r}", flush=True)
    check(rel <= 2 * rtol, f"path_costs vs path_costs_ref within {2 * rtol!r}")
    check(bool(np.all(np.abs(got - exact) <= rtol * exact)),
          f"path_costs vs the float64 sum within {rtol!r}")


def saturation_on_chip(fp, mon: _Jax) -> None:
    """Certified UGAL saturation and the uncertified batched engine on the
    same paths."""
    with _Phase("pf79_certified_saturation", mon):
        res = saturation_throughput(fp, tol=TOL, certify=True,
                                    cert_iters=CERT_ITERS)
    c = res.cert
    print(f"certified sat={res.value!r} bracket=[{res.sat_lo!r}, "
          f"{res.sat_hi!r}] gap={c.gap!r} iters={c.iters} "
          f"converged={c.converged} util=[{c.util_lb!r}, {c.util_ub!r}]",
          flush=True)
    with _Phase("pf79_batched_saturation", mon):
        sat = saturation_throughput(fp, tol=TOL, iters=BATCH_ITERS)
    print(f"batched sat={sat!r} iters={BATCH_ITERS}", flush=True)
    check(res.sat_lo <= res.value <= res.sat_hi,
          "certified saturation inside its certified bracket")
    err = abs(res.value - sat)
    check(err <= 2 * TOL + 0.02,
          f"certified vs batched |{err!r}| <= {2 * TOL + 0.02!r}")


def packets_on_chip(fp, mon: _Jax) -> None:
    """Packet engine on the PF(79) fluid paths.  random_perm at p = 40
    offers 3 packets per flow per cycle at load 0.3, and a router injects
    at most one packet per cycle, so most packets stay queued at their
    source: the run is injection-bound by design of the engine."""
    with _Phase("pf79_packet_workload", mon):
        wl = make_workload(fp, PACKET_LOAD, PACKET_CYCLES, seed=0,
                           max_packets=10_000_000)
    with _Phase("pf79_packet_engine", mon):
        res = simulate_packets(wl)
    in_flight = int(res.occ_sum[-1])
    print(f"pf79 packets={wl.num_packets} admitted={res.admitted} "
          f"delivered={res.num_delivered} dropped={res.num_dropped} "
          f"in_flight={in_flight}", flush=True)
    check(res.num_delivered + res.num_dropped + in_flight == res.admitted
          <= wl.num_packets and res.num_delivered > 0,
          "pf79 packets conserved (delivered + dropped + in flight "
          "== admitted)")
    print(f"pf79 tails={res.tails()}", flush=True)


def sharded_routing(mon: _Jax) -> None:
    """`build_blocked_routing` on four chips against the host backend:
    sampled destination columns equal bit for bit, and every block's span
    names the chip that computed it."""
    devs = jax.devices()
    check(len(devs) >= 4, f"four devices visible (found {len(devs)})")
    g = build_polarfly(79).graph
    rec = Recorder()
    with _Phase("pf79_routing_sharded", mon), recording(rec):
        rt = build_blocked_routing(g, backend="sharded", devices=4)
    used = _block_devices(rec)
    print(f"sharded diameter sweep ran on device ids {sorted(used)}",
          flush=True)
    check(used == {d.id for d in devs[:4]},
          "sharded diameter sweep spread over all four devices")
    used = routing_matches_host(rt, mon, "pf79_sharded")
    print(f"sharded columns ran on device ids {sorted(used)}", flush=True)
    check(used == {d.id for d in devs[:4]},
          "sharded destination columns spread over all four devices")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the sharded routing build")
    args = ap.parse_args(argv)

    cache = use_compile_cache(ROOT)
    mon = _Jax()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX default device is "
              f"{dev.platform}); nothing was run", file=sys.stderr)
        return 1
    print(f"device platform={dev.platform} kind={dev.device_kind} "
          f"count={len(jax.devices())} jax={jax.__version__} "
          f"compile_cache={cache}", flush=True)

    if args.chips == 4:
        sharded_routing(mon)
    else:
        packet_reference_pf13(mon)
        fp = build_pf79(mon)
        path_costs_on_chip(fp, mon)
        saturation_on_chip(fp, mon)
        packets_on_chip(fp, mon)
    print(f"compile_s_total={mon.compile_s:.3f} cache_hits={mon.hits} "
          f"cache_misses={mon.misses}", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
