"""Certified fluid solver: duality-gap certificates, utilization brackets,
bisection early exits, and the fp64 gating of `certify=True`.

The load-bearing property is bound dominance: on an instance where the
exact equilibrium is known (via a long-budget certified reference run),
a short-budget certificate's bracket must contain the true max
utilization and its error bound must dominate the iterate's true
distance to equilibrium.  Everything else checks the public contract:
certified and batched saturation agree at the stated tolerance (intact
and damaged PF(13)), oblivious modes certify exactly, deeply infeasible
probes exit early on the potential-mass bound, and float64 certification
refuses to run without JAX_ENABLE_X64 instead of silently truncating.
"""
import functools

import numpy as np
import pytest

from repro.core.polarfly import build_polarfly
from repro.core.routing import build_routing
from repro.simulation import (Certificate, CertifiedResult, build_flow_paths,
                              evaluate_load, latency_curve, make_pattern,
                              saturation_throughput)
from repro.simulation import fluid


@functools.lru_cache(maxsize=None)
def _fp(mode: str, damaged: bool = False):
    pf = build_polarfly(13)
    if damaged:
        g = pf.graph.subgraph_without_edges(pf.graph.edge_list[::7][:6])
        rt = build_routing(g)
    else:
        rt = build_routing(pf.graph, pf)
    pat = make_pattern("random_perm", rt, p=7, seed=0)
    kw = {} if mode == "min" else dict(k_candidates=6, seed=5)
    return build_flow_paths(rt, pat, mode, **kw)


# ---------------------------------------------------------------------------
# certificates on oblivious modes are exact
# ---------------------------------------------------------------------------

def test_oblivious_certificate_is_exact():
    fp = _fp("min")
    res = saturation_throughput(fp, tol=0.02, certify=True)
    assert isinstance(res, CertifiedResult)
    assert res.cert.kind == "exact"
    assert res.cert.gap == 0.0
    assert res.cert.util_err_bound == 0.0
    assert res.cert.converged
    # the oblivious split is its own fixed point: certified == batched
    assert res.value == saturation_throughput(fp, tol=0.02)
    el = evaluate_load(fp, 0.05, certify=True)
    assert el.cert.util_lb == el.cert.util_ub == pytest.approx(
        el.value.max_util, rel=1e-6)


# ---------------------------------------------------------------------------
# certified vs batched saturation at the stated tolerance
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mode", ["ugal", "ugal_pf"])
@pytest.mark.parametrize("damaged", [False, True])
def test_certified_saturation_agrees_with_batched(mode, damaged):
    fp = _fp(mode, damaged)
    sat_b = saturation_throughput(fp, tol=0.02, iters=3000)
    res = saturation_throughput(fp, tol=0.02, certify=True, cert_iters=3000)
    assert abs(res.value - sat_b) <= 0.06
    assert res.cert.kind == ("duality-gap" if mode == "ugal"
                             else "gated-residual")
    assert np.isfinite(res.cert.gap)
    assert res.cert.iters > 0
    # the certified bracket is sound: the measured saturation never falls
    # below the certified-feasible frontier, and the bracket is ordered
    assert res.sat_lo <= res.value + 1e-6
    assert res.sat_lo <= res.sat_hi + 1e-6


# ---------------------------------------------------------------------------
# bound dominance against a long-budget reference equilibrium
# ---------------------------------------------------------------------------

def test_certificate_bound_dominates_true_distance():
    """The whole point of the certificate: on mode="ugal" (whose target is
    the true linear-minimization oracle, so the gap is theorem-grade) the
    short-budget bracket must contain the exact max utilization and the
    error bound must dominate the iterate's actual distance to it."""
    fp = _fp("ugal")
    ref = evaluate_load(fp, 0.2, certify=True, util_tol=1e-6,
                        cert_iters=65536)
    mu_star = ref.value.max_util
    # the reference run is itself certified: its bracket brackets it
    assert ref.cert.util_lb - 1e-6 <= mu_star <= ref.cert.util_ub + 1e-6
    assert ref.cert.util_err_bound < 0.1

    short = evaluate_load(fp, 0.2, certify=True, util_tol=1e-6,
                          cert_iters=4096)
    assert short.cert.util_lb - 1e-6 <= mu_star <= short.cert.util_ub + 1e-6
    true_err = abs(short.value.max_util - mu_star)
    assert true_err <= short.cert.util_err_bound + ref.cert.util_err_bound
    # more budget must not loosen the certificate
    assert ref.cert.util_err_bound <= short.cert.util_err_bound + 1e-6


# ---------------------------------------------------------------------------
# early exits: certified decisions cut probe budgets
# ---------------------------------------------------------------------------

def test_decide_at_early_exit_on_clear_probes():
    fp = _fp("ugal")
    eidx, loads_rep, valid, is_min, first_edge, demand, _ = fp.device_arrays()
    fw = fluid._fw_pieces(eidx, loads_rep[1:], loads_rep[0], valid, is_min,
                          first_edge, fp.num_links, fp.mode)
    # deeply infeasible: the potential-mass bound certifies mu* > 1 in a
    # few strides even though the Bregman bracket never can (the capped
    # integrand is linear above _RHO_CAP)
    _, _, _, mu_lb, _, it, done, _ = fw.cert_equilibrate(
        fw.init, demand.astype(np.float32) * 0.8, 20000, 0.05, decide_at=1.0)
    assert bool(done)
    assert float(mu_lb) > 1.0
    assert int(it) <= 20 * fluid._CERT_STRIDE
    # deeply feasible: the Bregman upper end certifies mu* <= 1 quickly
    _, _, _, _, mu_ub, it2, done2, _ = fw.cert_equilibrate(
        fw.init, demand.astype(np.float32) * 0.05, 20000, 0.05,
        decide_at=1.0)
    assert bool(done2)
    assert float(mu_ub) <= 1.0
    assert int(it2) <= 40 * fluid._CERT_STRIDE


# ---------------------------------------------------------------------------
# latency_curve certify path and knob validation
# ---------------------------------------------------------------------------

def test_latency_curve_certified_matches_single_solves():
    fp = _fp("ugal")
    el = evaluate_load(fp, 0.1, certify=True, cert_iters=512)
    # a single solve is the latency curve's program as a batch of one:
    # every field and the certificate agree exactly
    one = latency_curve(fp, [0.1], certify=True, cert_iters=512)
    assert len(one) == 1 and isinstance(one[0], CertifiedResult)
    assert one[0].value == el.value
    assert one[0].cert == el.cert
    # across batch widths XLA orders the vmapped reductions differently,
    # and an unconverged 512-step solve (bracket ~0.21 wide) carries that
    # rounding forward: agreement to the 1e-3 the module documents for
    # latency-curve entries against per-load solves
    lc = latency_curve(fp, [0.1, 0.3], certify=True, cert_iters=512)
    assert len(lc) == 2 and all(isinstance(r, CertifiedResult) for r in lc)
    assert lc[0].value.max_util == pytest.approx(el.value.max_util,
                                                 rel=1e-3)
    assert lc[0].cert.iters == el.cert.iters


def test_certify_knob_validation():
    fp = _fp("ugal")
    import jax
    if not jax.config.jax_enable_x64:
        with pytest.raises(ValueError, match="JAX_ENABLE_X64"):
            evaluate_load(fp, 0.2, certify=True, dtype="float64")
    with pytest.raises(ValueError, match="dtype"):
        evaluate_load(fp, 0.2, certify=True, dtype="bfloat16")


def test_certificate_is_exported():
    assert Certificate.__name__ == "Certificate"
    assert {"gap", "util_lb", "util_ub", "util_err_bound", "kind"} <= set(
        Certificate.__dataclass_fields__)


# ---------------------------------------------------------------------------
# warm starts: after the first feasible probe, from the last feasible one
# ---------------------------------------------------------------------------

def test_probes_warm_start_from_the_last_feasible_probe():
    """Once a bisection probe is judged feasible, every later probe starts
    from that probe's split scaled to its own load, never from the iterate
    of an infeasible probe in between (which piles load past the delay cap
    and can leave a feasible load reading max_util > 1 at the budget).
    The first trace sample of a probe is its warm start's max utilization,
    the last sample of a probe its final one."""
    fp = _fp("ugal")
    res = saturation_throughput(fp, tol=0.02, certify=True, cert_iters=256,
                                trace=True)
    tr = res.trace
    offered, feasible = tr.brackets[:, 0], tr.brackets[:, 1] > 0
    assert feasible.any() and not feasible.all()
    last_feasible, checked = None, 0
    for p in range(len(offered)):
        mu = tr.max_util[tr.probe == p]
        if last_feasible is not None:
            f, mu_f = last_feasible
            assert mu[0] == pytest.approx(mu_f * offered[p] / offered[f],
                                          rel=1e-5)
            checked += not feasible[p - 1]
        if feasible[p]:
            last_feasible = (p, mu[-1])
    # at least one probe followed an infeasible one after a feasible one
    assert checked


# ---------------------------------------------------------------------------
# near-boundary bracket regression (ROADMAP open item, pinned)
# ---------------------------------------------------------------------------

def test_near_boundary_bracket_pinned_at_default_budget():
    """Near-boundary saturation probes exhaust the default `cert_iters`
    budget before deciding, so the certified bracket stays wider than the
    bisection tolerance (ROADMAP open item).  Pin the bracket at the
    default budget -- currently [0.3125, 0.5] for the PF(13) random-perm
    UGAL probe -- so future infeasibility-certificate tightening is
    measured, not anecdotal: the bracket must never drift more than one
    bisection grid step looser, and must keep bracketing the batched
    saturation value at a budget where that engine has converged (iters
    3000, see the fluid module docstring: at 250 it reads 0.25, under the
    certified-feasible 0.3125)."""
    fp = _fp("ugal")
    tol = 0.05
    res = saturation_throughput(fp, tol=tol, certify=True)
    sat = saturation_throughput(fp, tol=tol, iters=3000)
    assert res.sat_lo >= 0.3125 - tol / 2
    assert res.sat_hi <= 0.5 + tol / 2
    assert res.sat_lo <= sat <= res.sat_hi
    # the mid-band is still undecided at the default budget; when an
    # adaptive per-probe budget or a sharper infeasibility certificate
    # closes it, this assertion (and the ROADMAP item) should go
    assert res.sat_hi - res.sat_lo >= tol
