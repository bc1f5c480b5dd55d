"""Batched vs scalar fluid-solver engines -- the acceptance microbenchmark
for the in-jit warm-started saturation bisection (tentpole of the batched
solver PR).

Sweep: PF(13) adaptive modes (UGAL / UGAL_PF) on the Fig. 8/9 adversarial
patterns (random_perm, tornado) at convergence-grade iters, where the two
engines agree on the saturation (see fluid.py docstring).  Asserts >= 3x
aggregate wall-clock unless BENCH_SMOKE=1, plus a vmapped latency-curve
comparison.

The certified section is the acceptance microbenchmark of the certified
Frank-Wolfe PR: on the UGAL adaptive point, `certify=True` (conjugate
line-search probes with duality-gap early exits) must match a
4x-longer-budget batched reference run at least as closely as the
tolerance while beating its wall clock -- the conjugate probes converge
each bisection decision in a fraction of the harmonic schedule's steps,
so the certified engine is simultaneously faster and backed by a real
bound.  BENCH_LARGE=1 re-runs the comparison on the PF(79) adaptive
point through the blocked path stack."""
from repro.core.polarfly import build_polarfly
from repro.core.routing import build_blocked_routing, build_routing
from repro.simulation import (build_flow_paths, evaluate_load, latency_curve,
                              make_pattern, saturation_throughput)

from .common import emit, large, smoke, timed

ITERS = 2000
TOL = 0.005


def _certified_point(tag: str, fp, tol: float, check: bool,
                     cert_iters: int = ITERS):
    """Certified-vs-batched comparison at one adaptive point: the batched
    reference gets 4x the certified engine's iteration cap (harmonic
    probes need it -- see the truncation-noise discussion in fluid.py),
    the certified run carries its duality-gap certificate into the
    emitted row, and `check` enforces the acceptance bar."""
    ref_iters = 4 * ITERS
    saturation_throughput(fp, tol=tol, iters=ref_iters)  # compile
    sat_ref, us_ref = timed(lambda: saturation_throughput(
        fp, tol=tol, iters=ref_iters))
    saturation_throughput(fp, tol=tol, certify=True, cert_iters=cert_iters)
    res, us_c = timed(lambda: saturation_throughput(
        fp, tol=tol, certify=True, cert_iters=cert_iters))
    err = abs(res.value - sat_ref)
    emit(f"{tag}.certified", us_c,
         f"sat={res.value:.4f};gap={res.cert.gap:.3e};lo={res.sat_lo:.4f};"
         f"hi={res.sat_hi:.4f};iters={res.cert.iters};err_vs_ref={err:.4f};"
         f"speedup={us_ref / us_c:.2f}x")
    emit(f"{tag}.reference", us_ref, f"sat={sat_ref:.4f};iters={ref_iters}")
    if check:
        assert err <= 2 * tol + 0.02, \
            f"certified saturation off reference by {err:.4f}"
        assert us_c < us_ref, \
            f"certified {us_c:.0f}us not faster than {us_ref:.0f}us reference"


def _obs_noop_overhead():
    """Acceptance bar for the obs layer: with the default NullRecorder
    installed, the public `saturation_throughput` must cost within 2% of
    dispatching the underlying jitted bisection directly (the
    uninstrumented baseline).  PF(7) keeps the device work small enough
    that any per-call host overhead from the span plumbing would show;
    min-of-N wall clocks on both sides squeeze out scheduler noise."""
    import numpy as np

    from repro.simulation.fluid import _probe_schedule, _saturation_batch

    pf = build_polarfly(7)
    rt = build_routing(pf.graph, pf)
    pat = make_pattern("random_perm", rt, p=4, seed=0)
    fp = build_flow_paths(rt, pat, "ugal", k_candidates=8, seed=0)
    probes = max(1, int(np.ceil(np.log2(1.0 / TOL))))
    sched = _probe_schedule(ITERS, probes)
    eidx, loads_rep, valid, is_min, first_edge, demand, _ = fp.device_arrays()

    def raw():
        return float(_saturation_batch(
            eidx, loads_rep[1:], loads_rep[0], valid, is_min, first_edge,
            demand, fp.num_links, fp.mode, ITERS, sched)[0])

    def pub():
        return saturation_throughput(fp, tol=TOL, iters=ITERS,
                                     engine="batched")

    assert raw() == pub()  # compile both; identical jit underneath
    # interleave the A/B pairs so machine-load drift hits both sides
    # equally; min-of-N on each side then cancels transient contention
    reps = 7
    pairs = [(timed(raw)[1], timed(pub)[1]) for _ in range(reps)]
    us_raw = min(r for r, _ in pairs)
    us_pub = min(p for _, p in pairs)
    ratio = us_pub / us_raw
    emit("fluid.pf7.obs_noop_overhead", us_pub,
         f"baseline_us={us_raw:.1f};ratio={ratio:.3f}x")
    assert us_pub <= 1.02 * us_raw, \
        f"no-op recorder path {us_pub:.1f}us vs raw {us_raw:.1f}us " \
        f"({ratio:.3f}x > 1.02x)"


def _run_large():
    """PF(79) adaptive point (6321 routers) through the blocked stack:
    the certified engine must keep its win at the scale tier."""
    g = build_polarfly(79).graph
    rt = build_blocked_routing(g)
    p = g.params.get("radix", 80) // 2
    pat = make_pattern("random_perm", rt, p=p, seed=0, max_flows=60_000)
    fp = build_flow_paths(rt, pat, "ugal", k_candidates=10, seed=0)
    # conjugate probes are grid-exact long before 1000 iterations at this
    # scale; the full ITERS cap only pads out probes whose feasible-side
    # certificate cannot close at fp32 anyway (see ROADMAP)
    _certified_point("fluid.pf79.random_perm.ugal", fp, 0.01, check=True,
                     cert_iters=ITERS // 2)


def run():
    q = 7 if smoke() else 13
    pf = build_polarfly(q)
    rt = build_routing(pf.graph, pf)
    p = (q + 1) // 2
    total_scalar = total_batched = 0.0
    for pattern in ("random_perm", "tornado"):
        pat = make_pattern(pattern, rt, p=p, seed=0)
        for mode in ("ugal", "ugal_pf"):
            fp = build_flow_paths(rt, pat, mode, k_candidates=8, seed=0)
            # compile both engines outside the timed region
            evaluate_load(fp, 0.5, iters=ITERS)
            saturation_throughput(fp, tol=TOL, iters=ITERS, engine="batched")
            sat_s, us_s = timed(lambda: saturation_throughput(
                fp, tol=TOL, iters=ITERS, engine="scalar"))
            sat_b, us_b = timed(lambda: saturation_throughput(
                fp, tol=TOL, iters=ITERS, engine="batched"))
            total_scalar += us_s
            total_batched += us_b
            emit(f"fluid.pf{q}.{pattern}.{mode}.batched", us_b,
                 f"sat={sat_b:.3f};speedup={us_s / us_b:.1f}x")
            emit(f"fluid.pf{q}.{pattern}.{mode}.scalar", us_s,
                 f"sat={sat_s:.3f}")

    # latency sweep: one vmapped call vs per-load dispatch
    pat = make_pattern("random_perm", rt, p=p, seed=0)
    fp = build_flow_paths(rt, pat, "ugal_pf", k_candidates=8, seed=0)
    loads = [0.1 * i for i in range(1, 10)]
    latency_curve(fp, loads, engine="batched")
    evaluate_load(fp, 0.5)
    _, us_b = timed(lambda: latency_curve(fp, loads, engine="batched"))
    _, us_s = timed(lambda: latency_curve(fp, loads, engine="scalar"))
    emit(f"fluid.pf{q}.latency_curve.batched", us_b,
         f"P={len(loads)};speedup={us_s / us_b:.1f}x")

    speedup = total_scalar / total_batched
    emit(f"fluid.pf{q}.saturation.total", total_batched,
         f"speedup={speedup:.1f}x")
    if not smoke():
        assert speedup >= 3.0, \
            f"batched saturation sweep speedup {speedup:.1f}x < 3x"

    # certified engine: gap-driven conjugate probes vs the batched
    # harmonic schedule at the budget it needs for comparable accuracy
    pat = make_pattern("random_perm", rt, p=p, seed=0)
    fp = build_flow_paths(rt, pat, "ugal", k_candidates=8, seed=0)
    _certified_point(f"fluid.pf{q}.random_perm.ugal", fp, TOL,
                     check=not smoke())
    _obs_noop_overhead()
    if large() and not smoke():
        _run_large()


if __name__ == "__main__":
    run()
