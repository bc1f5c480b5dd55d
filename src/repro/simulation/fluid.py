"""Fluid-flow network simulator (JAX), reproducing the §VIII methodology.

Instead of per-flit cycle-accurate simulation (BookSim), flows are fluids
split across candidate paths.  Adaptive modes (UGAL / UGAL_PF) converge to a
Wardrop equilibrium of the queueing congestion game via Frank-Wolfe on the
Beckmann potential -- the fluid analogue of UGAL's "compare local queue
occupancy, take the cheaper path" rule, iterated to steady state:

  cost(candidate) = sum over its links of (1 + w(rho)),  w = M/D/1 delay
  split <- (1 - 2/(t+2)) * split + 2/(t+2) * one_hot(argmin cost)

UGAL_PF additionally applies the paper's 2/3 adaptation threshold: a flow
adapts away from its minimal path only to the extent the first (local)
min-path link exceeds 2/3 utilization.

Oblivious modes: `min` puts everything on the unique minimal path;
`valiant`/`cvaliant`/`ecmp` split uniformly across their candidates.

Outputs: per-link utilization, accepted throughput (saturation = largest
offered load with max utilization <= 1), and mean latency in cycles
(1 cycle router pipeline per hop + queueing delay).

Every solve traces one Frank-Wolfe core (`_fw_pieces`) inside one of five
jitted programs, one per question:

  * `_solve_batch` -- the cold-start equilibrium, vmapped over a vector
    of offered loads: `latency_curve` (the whole sweep in one call) and
    `evaluate_load` (a batch of one).
  * `_saturation_batch` -- `saturation_throughput`'s default
    ``engine="batched"``: the bisection as an in-jit unrolled probe loop
    (ceil(log2(1/tol)) probes, the scalar bisection's probe sequence),
    each probe's split warm-started from the previous probe's
    equilibrium.  The Wardrop fixed point does not depend on the starting
    split, so warm probes re-converge in a fraction of `iters` steps
    (`_probe_schedule`: iters/2 for the first half-range jump, iters/4
    for the next four, iters/8 for the fine tail).
  * `_truncation_gap` -- `truncation_error`.
  * `_certified_batch` -- `certify=True` on `latency_curve` and
    `evaluate_load` (again a batch of one).
  * `_certified_saturation` -- `certify=True` on `saturation_throughput`.

``trace=True`` is a static argument of the same programs, not a program of
its own: the traced step computes the same split and adds the samples.
``engine="scalar"`` on `saturation_throughput` / `latency_curve` is the
executable reference, one `evaluate_load` per probe or load, every probe
cold-started (the same two-engine pattern the path builders use,
`build_flow_paths`).

Equivalence (tests/test_simulation.py): oblivious modes (min / ecmp /
valiant / cvaliant) have load-independent splits, so batched probes are
exact replicas of scalar probes and saturations agree within any `tol`;
latency-curve entries match per-load `evaluate_load` within 1e-3 relative
in every mode (XLA orders the vmapped reductions by batch width, and a
truncated solve carries that rounding forward; `evaluate_load(x)` is
`latency_curve([x])[0]` exactly).  Adaptive modes (UGAL / UGAL_PF) carry
intrinsic O(1/iters) truncation noise -- near saturation the adaptation
gate flattens max-utilization to ~0.98 over a wide load range, so the
feasibility boundary of a *truncated* Frank-Wolfe run keeps drifting with
the iteration budget (e.g. PF(13) random-perm UGAL_PF saturation moves
0.41 -> 0.47 between iters=250 and 2000).  Warm-started probes follow a
different truncation trajectory than cold-started ones, so the engines
agree only as tightly as the solves are converged: within `tol` = 0.05 at
iters >= 3000 on PF(13) adversarial patterns, and asymptotically as iters
grows.

Certified engine (``certify=True`` on the public entry points): instead of
trusting a fixed iteration budget, the solver computes the Frank-Wolfe
duality gap

  g(split) = sum_f demand_f * <split_f - target_f, cost_f>  >=  Phi - Phi*

and drives everything off it.  The steps are conjugate Frank-Wolfe with an
exact line search on the Beckmann potential (Mitradjieva-Lindberg CFW:
vanilla FW's O(1/t) zigzag is far too slow to certify anything; UGAL_PF
keeps the uncertified engines' harmonic steps, since its gated target is
not an oracle and line search on the potential is meaningless).  The gap
is turned into a *certified max-utilization bracket* [util_lb, util_ub]
by per-link Bregman localization (`_util_interval`): Phi is separable
across links and the equilibrium loads are optimal over the feasible load
polytope, so Phi(rho) - Phi* >= D_e(rho_e, rho*_e) per link, and each
rho*_e lies where the per-link divergence stays <= g.  The near-saturated
links that decide feasibility sit in the high-curvature region of the
M/D/1 delay, so their intervals are orders of magnitude tighter than the
global 2*sqrt(g) strong-convexity bound -- that is what makes the
certificate reachable at practical budgets.  A bisection probe is
*certified feasible* when util_ub <= 1 and *certified infeasible* when
util_lb > 1, and `_certified_saturation` uses those decisions to
early-exit each in-jit warm-started probe (lax.while_loop over strided
step chunks) instead of running a fixed budget.  The per-iteration
best-response cost reduction is routed through
`kernels.minplus.path_costs` (one XLA gather on every backend).  Tight
brackets need small gaps, and the
fp32 gap has an inner-product-cancellation noise floor (~1e-3 * total
demand): set JAX_ENABLE_X64=1 and the certified engine picks float64
automatically (tighter default util_tol) while the uncertified engines
stay pinned to float32.  For mode="ugal" the gap is a true duality gap
(theorem-grade bracket); for mode="ugal_pf" the gated target makes |g| a
fixed-point residual (`Certificate.kind = "gated-residual"`, empirically
validated by tests); oblivious splits are exact fixed points (gap
identically 0).
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Callable, List, NamedTuple

import numpy as np

import jax
import jax.numpy as jnp

from ..kernels.minplus.ops import path_costs
from ..obs.record import get_recorder
from ..obs.trace import ConvergenceTrace
from .paths import _MXU_LANES, FlowPaths

__all__ = ["FluidResult", "SaturationResult", "Certificate",
           "CertifiedResult", "evaluate_load", "saturation_throughput",
           "truncation_error", "latency_curve"]

_EPS = 1e-6
_RHO_CAP = 0.999
_BUF_PACKETS = 32.0  # 128-flit input buffers, 4-flit packets (paper §VIII-A)
# Warm-started probes resume the step-size schedule at this t: the first
# warm step moves 2/(t+2) = 1/3 of the way to the current best response,
# instead of gamma(0) = 1 which would discard the carried split entirely.
_WARM_T0 = 4.0
# Certified runs check the duality gap (and the early-exit decision) once
# per chunk of this many line-searched steps, and refresh the incrementally
# updated link loads from the split at the same cadence.
_CERT_STRIDE = 32


@dataclass
class FluidResult:
    offered: float  # per-endpoint offered load (fraction of injection bw)
    accepted: float  # per-endpoint accepted throughput
    max_util: float
    mean_latency: float  # cycles
    mean_hops: float
    # convergence telemetry when the solve ran with trace=True (None
    # otherwise); carried out of jit as fixed-size sample buffers and
    # assembled host-side (repro.obs.trace.ConvergenceTrace)
    trace: ConvergenceTrace = None


@dataclass
class SaturationResult:
    """`saturation_throughput(..., trace=True)` payload on the uncertified
    engine: the saturation and the per-probe convergence telemetry."""
    saturation: float
    trace: ConvergenceTrace


@dataclass
class Certificate:
    """Convergence certificate attached to every `certify=True` result.

    `gap` is the Frank-Wolfe duality gap at the reported iterate, and
    `[util_lb, util_ub]` the certified bracket it induces on the *exact*
    Wardrop-equilibrium max link utilization via per-link Bregman
    localization of the Beckmann potential (`_util_interval`): both the
    measured max_util and the exact equilibrium's lie inside it, and
    `util_err_bound = util_ub - util_lb` is the bracket width the
    `util_tol` stopping rule acts on.  The bracket is theorem-grade when
    `kind == "duality-gap"` (mode="ugal": the target is the true
    linear-minimization oracle, so gap >= Phi - Phi*).  For mode="ugal_pf"
    the 2/3-occupancy gate biases the target away from the oracle, so
    |gap| is a fixed-point residual (`kind == "gated-residual"`): the same
    stopping rule and the same bracket formula, empirically validated
    rather than proven.  Oblivious splits are exact fixed points: gap is
    identically 0, the bracket has zero width, and `kind == "exact"`.

    `converged` is True when the run exited on the bracket test
    (util_err_bound <= util_tol) or, for saturation probes, on a certified
    feasibility decision -- False means the `cert_iters` budget ran out
    first, and `gap` / the bracket report how far the run actually got
    (still valid bounds).  `dtype` records the certification precision
    ("float64" requires JAX_ENABLE_X64=1, see docs/benchmarks.md).
    """
    gap: float
    util_lb: float
    util_ub: float
    util_err_bound: float
    util_tol: float
    iters: int
    dtype: str
    converged: bool
    kind: str


@dataclass
class CertifiedResult:
    """A certified value plus its `Certificate`.

    `value` is whatever the uncertified call would have returned
    (`FluidResult` for `evaluate_load`/`latency_curve`, the saturation
    float for `saturation_throughput`).  For saturations, `[sat_lo,
    sat_hi]` is the *certified* bracket: every probe at or below `sat_lo`
    was certified feasible (util_ub <= 1) and every probe at or above
    `sat_hi` certified infeasible (util_lb > 1), so the exact saturation
    load of the equilibrium model lies in the bracket (up to the bisection
    grid); the point value keeps the uncertified engines' convention
    (largest probed load with measured max_util <= 1).  NaN bracket fields
    on non-saturation results.
    """
    value: object
    cert: Certificate
    sat_lo: float = float("nan")
    sat_hi: float = float("nan")
    # per-stride convergence telemetry when trace=True (None otherwise);
    # trace.final_gap equals cert.gap -- the trace's last sample is
    # written from the same carried gap the certificate is built from
    trace: ConvergenceTrace = None


def _queue_delay(rho: jnp.ndarray) -> jnp.ndarray:
    """M/D/1 waiting time, capped near saturation."""
    r = jnp.clip(rho, 0.0, _RHO_CAP)
    return r / (2.0 * (1.0 - r))


def _queue_delay_prime(rho: jnp.ndarray) -> jnp.ndarray:
    """d/drho of `_queue_delay` below the cap: 1/(2(1-rho)^2) -- the
    diagonal Beckmann Hessian the conjugate-direction combination uses."""
    r = jnp.clip(rho, 0.0, _RHO_CAP)
    return 1.0 / (2.0 * (1.0 - r) ** 2)


# w(_RHO_CAP): the slope of the Beckmann integrand in the clipped region
_W_CAP = _RHO_CAP / (2.0 * (1.0 - _RHO_CAP))


def _w_integral(r: jnp.ndarray) -> jnp.ndarray:
    """W(r) = int_0^r w(s) ds for the capped M/D/1 delay `_queue_delay`:
    (1/2)(-log(1-r) - r) below the cap, linear with slope w(cap) above."""
    rc = jnp.clip(r, 0.0, _RHO_CAP)
    return 0.5 * (-jnp.log1p(-rc) - rc) + _W_CAP * jnp.maximum(r - _RHO_CAP,
                                                               0.0)


def _bregman(x: jnp.ndarray, y: jnp.ndarray) -> jnp.ndarray:
    """Per-link Bregman divergence of the Beckmann integrand,
    D(x, y) = W(x) - W(y) - w(y)(x - y) >= 0, zero iff x == y (up to the
    zero-curvature region above the cap).  The linear '1 +' part of the
    link cost cancels in the divergence."""
    return _w_integral(x) - _w_integral(y) - _queue_delay(y) * (x - y)


def _util_interval(rho, gap, num_links: int, ymax: float = 4.0):
    """Certified bracket [mu_lb, mu_ub] for the exact Wardrop equilibrium's
    max link utilization, given Phi(rho) - Phi* <= gap with `rho` feasible.

    Phi is separable across links and rho* is first-order optimal over the
    feasible load polytope (rho is a member), so

      Phi(rho) - Phi*  =  grad Phi(rho*) . (rho - rho*) + sum_e D_e
                       >=  D(rho_e, rho*_e)   for every link e separately,

    i.e. each rho*_e lies in the interval where the per-link Bregman
    divergence `_bregman(rho_e, .)` stays <= gap.  The divergence is
    monotone on either side of rho_e, so the interval ends invert by
    bisection (vectorized over links).  Then max_e lower_e <= mu* <=
    max_e upper_e.  This localization is what makes the certificate
    usable: the near-saturated links that decide feasibility sit in the
    high-curvature region w'(rho) ~ 1/(2(1-rho)^2), where the interval is
    orders of magnitude tighter than the global strong-convexity bound
    2*sqrt(gap).  Links whose upper interval end exceeds `ymax` report
    +inf (the divergence stops growing only above the cap, so by
    ymax = 4 that means the gap is still huge)."""
    if not num_links:
        z = jnp.zeros((), rho.dtype)
        return z, z
    g = jnp.maximum(gap, 0.0)

    def shrink(_, lohi):
        # invariant: D(rho, inner) <= g, outer is on the far side
        inner, outer = lohi
        mid = 0.5 * (inner + outer)
        ok = _bregman(rho, mid) <= g
        return (jnp.where(ok, mid, inner), jnp.where(ok, outer, mid))

    hi0 = jnp.full_like(rho, ymax)
    up, _ = jax.lax.fori_loop(0, 60, shrink, (rho, hi0))
    up = jnp.where(_bregman(rho, hi0) <= g, jnp.inf, up)
    dn, _ = jax.lax.fori_loop(0, 60, shrink, (rho, jnp.zeros_like(rho)))
    return jnp.max(dn), jnp.max(up)


def _phi_mass_lower_bound(phi_star_lb, traversals, ymax: float = 4.0):
    """Potential-mass lower bound on the equilibrium max utilization.

    The Bregman localization above is blind on the infeasible side: the
    capped integrand is linear above `_RHO_CAP`, so no gap can distinguish
    rho* = 1.001 from rho* = 4 there.  This closes that hole with a mass
    argument: if mu* <= m, then per-link convexity gives phi(rho*_e) <=
    rho*_e * phi(m)/m, and the total load is conserved --
    sum_e rho*_e <= `traversals` (total demand weighted by each flow's
    longest candidate path) -- so Phi* <= (phi(m)/m) * traversals.  Given
    `phi_star_lb` <= Phi* (the Frank-Wolfe lower bound Phi(rho) - gap),
    every m violating that inequality is excluded: the largest excluded m
    (monotone, found by bisection) is a certified lower bound on mu*.
    Returns 0 when nothing is excluded; deeply infeasible loads are
    excluded quickly because their overload mass makes Phi(rho) - gap huge
    relative to the feasible-potential ceiling."""
    def excluded(m):
        m = jnp.maximum(m, 1e-6)
        return phi_star_lb > (m + _w_integral(m)) / m * traversals

    def half(_, lohi):
        lo, hi = lohi
        mid = 0.5 * (lo + hi)
        return jnp.where(excluded(mid), mid, lo), jnp.where(excluded(mid),
                                                            hi, mid)

    z = jnp.zeros_like(phi_star_lb)
    lo, _ = jax.lax.fori_loop(0, 60, half, (z, jnp.full_like(z, ymax)))
    return lo


class _FWPieces(NamedTuple):
    """`_fw_pieces` bundle; see its docstring for the field contracts."""
    init: jnp.ndarray
    equilibrate: Callable
    loads: Callable
    cost_of: Callable
    fw_target: Callable
    target_of: Callable
    gap_of: Callable
    cert_equilibrate: Callable


def _fw_pieces(eidx, loads_arrays, loads_kind, valid, is_min, first_edge,
               num_links: int, mode: str, dtype=jnp.float32) -> _FWPieces:
    """Shared Frank-Wolfe building blocks, traced inside each jitted entry.

    Returns a `_FWPieces` namedtuple:

      init              [F, K] mode-dependent starting split.
      equilibrate(split0, demand, iters, t0=0.0, trace=False)
                        -> (split, ys): `iters` Frank-Wolfe steps from
                        `split0` using step sizes 2/(t+2) for t = t0,
                        t0+1, ...; identity for oblivious modes (their
                        split is the fixed point).  With `trace` (static),
                        `ys` holds the per-step (gap [iters], max_util
                        [iters], gamma [iters]) samples -- one zero-gap
                        sample for oblivious modes -- and is `()` without.
      loads(split, demand) -> rho [E]
      cost_of(rho)      -> per-candidate path cost [F, K], routed through
                        `kernels.minplus.path_costs`.
      fw_target(split, rho) -> [F, K] Frank-Wolfe best-response target
                        (adaptive modes only; includes the UGAL_PF gate),
                        the same per-step math `equilibrate` applies, for
                        the truncation-error probe and UGAL_PF's
                        certified step.
      target_of(split, rho, cost) -> fw_target with the masked cost
                        precomputed (the certified path needs the raw cost
                        for the gap as well, so it computes cost once).
      gap_of(split, target, cost, demand) -> scalar Frank-Wolfe duality
                        gap sum_f demand_f * <split_f - target_f, cost_f>.
      cert_equilibrate(split0, demand, max_iters, util_tol, t0, decide_at,
                        trace_cap)
                        gap-driven conjugate line-search Frank-Wolfe; see
                        below.

    `dtype` pins the arithmetic precision of every closure (the uncertified
    engines always pass float32 -- explicitly, so enabling JAX_ENABLE_X64
    for a certified run does not silently promote them; certified runs pass
    float64 when x64 is enabled).

    Link loads use the incidence structure from `FlowPaths.device_arrays`:
    a padded per-edge gather matrix in the common case (XLA:CPU serializes
    scatter-adds, so the dense gather + row-sum is ~5x faster per
    Frank-Wolfe iteration at ~1e-4 relative float32 rounding), or plain
    scatter-add for pathologically skewed incidence counts.  With the
    "mxu" kind, float32 loads are one matmul instead
    (`_mxu_link_loads`; its one-hot of the edge ids' high parts is built
    once per call, before the loops), and with "mxu_tiles" one matmul per
    tile of edge ids, over the candidate weights gathered into the tiles'
    edge order; float64 loads gather as "pad" with either.  Optimization
    barriers keep XLA from fusing the weight / delay tables into their
    consuming gathers, in every program, vmapped or not.

    Device scopes (`jax.named_scope`, in the op_names of a profile):
    `fluid.loads` (`loads`, every incidence path), `fluid.cost`
    (`cost_of`, around `minplus.path_costs`), `fluid.target`
    (`target_of`), `fluid.line_search` (the exact line search) and
    `fluid.certify` (the gap and bracket at each chunk boundary).

    `cert_equilibrate(split0, demand, max_iters, util_tol, t0=0.0,
    decide_at=None, trace_cap=0)` returns `(split, rho, gap, mu_lb,
    mu_ub, iters, converged, trace)`.  With `trace_cap > 0` (a static
    bound: chunks + 1), `trace` is a tuple of fixed-size per-chunk sample
    buffers `(iter, gap, max_util, mu_lb, mu_ub, gamma, count)` written
    in-loop with `.at[idx].set` -- NaN-padded past `count`, trimmed
    host-side into a `ConvergenceTrace`; `()` when tracing is off.
    It runs `_CERT_STRIDE`-step chunks inside a
    lax.while_loop.  For mode="ugal" each step is conjugate Frank-Wolfe
    with an exact line search on the Beckmann potential (bisection on the
    monotone directional derivative <delta_rho, 1 + w(rho + gamma *
    delta_rho)>; link loads updated incrementally since they are linear in
    the split); for mode="ugal_pf" each step is the uncertified engines'
    harmonic 2/(t0+t+2) step toward the gated target (line search on the
    potential is meaningless for the gated dynamic).  At every chunk
    boundary the link loads are refreshed from the split (shedding the
    incremental update's accumulated rounding), the duality gap is
    recomputed, and `_util_interval` turns it into the certified max-util
    bracket [mu_lb, mu_ub]; `mu_lb` is additionally maxed with the
    potential-mass bound (`_phi_mass_lower_bound`), which is what actually
    fires on deeply infeasible loads where the capped integrand's linear
    region blinds the Bregman bracket.  The loop exits early when the
    bracket is tighter than `util_tol` -- or, with `decide_at` set, as
    soon as the bracket certifies max_util* to be on either side of
    `decide_at` (the bisection early-exit).  Oblivious modes return
    immediately with gap 0 and a zero-width bracket.
    """
    minvec = jnp.where(is_min, 1.0, 0.0).astype(dtype)
    nmin = jnp.maximum(minvec.sum(axis=1, keepdims=True), 1)
    minvec = minvec / nmin
    uniform = (valid / jnp.maximum(valid.sum(axis=1, keepdims=True), 1)
               ).astype(dtype)
    has_alt = (valid & ~is_min).any(axis=1)
    # longest valid candidate path per flow, in links: any split satisfies
    # sum_e rho_e <= sum_f demand_f * lmax_f (the potential-mass
    # infeasibility certificate's load-conservation budget)
    lmax = jnp.where(valid, (eidx < num_links).sum(-1), 0).max(axis=1)

    mxu = (loads_kind in ("mxu", "mxu_tiles")
           and jnp.dtype(dtype) == jnp.float32)
    if mxu:
        with jax.named_scope("fluid.loads"):
            hi_onehot, lo_onehot = _mxu_factors(
                eidx.reshape(-1) if loads_kind == "mxu" else loads_arrays[2],
                num_links)

    @jax.named_scope("fluid.loads")
    def loads(split, demand):
        w = (split * demand[:, None]).reshape(-1)  # [F*K]
        if mxu and loads_kind == "mxu":
            wm = jnp.broadcast_to(w[:, None], (w.shape[0], eidx.shape[2]))
            return _mxu_link_loads(wm.reshape(-1), hi_onehot, lo_onehot,
                                   num_links)
        if loads_kind in ("pad", "mxu", "mxu_tiles"):
            w = jax.lax.optimization_barrier(
                jnp.concatenate([w, jnp.zeros(1, w.dtype)]))
            if mxu:  # the tiles' candidate weights, in edge order
                return _mxu_link_loads(w[loads_arrays[1]], hi_onehot,
                                       lo_onehot, num_links)
            return w[loads_arrays[0]].sum(axis=1)  # [E]
        # "scatter" fallback for pathologically skewed incidence counts:
        # slower, but rounding stays proportional to each edge's own load
        w3 = w.reshape(eidx.shape[0], eidx.shape[1], 1) \
            * (eidx < num_links).astype(w.dtype)
        rho = jnp.zeros(num_links + 1, w.dtype).at[eidx.reshape(-1)].add(w3.reshape(-1))  # reprolint: allow[scatter-add] -- deliberate fallback for pathologically skewed incidence where the padded gather would blow memory; FlowPaths.device_arrays picks the pad path whenever it fits
        return rho[:num_links]  # [E]

    @jax.named_scope("fluid.cost")
    def cost_of(rho):
        delay = 1.0 + _queue_delay(rho)
        d = jax.lax.optimization_barrier(
            jnp.concatenate([delay, jnp.zeros(1, delay.dtype)]))
        return path_costs(d, eidx)  # [F,K]

    @jax.named_scope("fluid.target")
    def target_of(split, rho, cost):
        target = jax.nn.one_hot(jnp.argmin(cost, axis=1), split.shape[1],
                                dtype=split.dtype)
        if mode == "ugal_pf":
            # the 2/3 local-occupancy adaptation threshold (paper
            # §VII-C): occupancy is of the 128-flit (32-packet) output
            # buffer, whose M/D/1 mean queue length only crosses 2/3
            # near rho ~ 0.98
            qlen = _queue_delay(rho[first_edge]) * rho[first_edge]  # Little
            gate = jnp.clip((qlen / _BUF_PACKETS - 2.0 / 3.0) * 8.0,
                            0.0, 1.0)
            gate = jnp.where(has_alt, gate, 0.0)
            target = gate[:, None] * target + (1 - gate)[:, None] * minvec
        return target

    def fw_target(split, rho):
        return target_of(split, rho, jnp.where(valid, cost_of(rho), jnp.inf))

    def gap_of(split, target, cost, demand):
        # per-flow inner products first: the gap is a difference of
        # near-equal inner products, and the per-flow form keeps the
        # cancellation local (each <split_f - target_f, cost_f> is already
        # O(gap_f)) instead of subtracting two global sums
        c = jnp.where(valid, cost, 0.0)
        per_flow = ((split - target) * c).sum(axis=1)
        return (demand * per_flow).sum()

    def equilibrate(split0, demand, iters: int, t0: float = 0.0,
                    trace: bool = False):
        # with `trace`, the gap is an extra O(F*K) reduction of the cost
        # the step computes anyway; samples stay on device as scan ys
        if mode not in ("ugal", "ugal_pf"):
            if not trace:
                return split0, ()
            mu = _max_util(loads(split0, demand), num_links).astype(dtype)
            z = jnp.zeros((1,), dtype)
            return split0, (z, mu[None], z)

        def body(split, t):
            rho = loads(split, demand)
            cost = cost_of(rho)
            target = target_of(split, rho, jnp.where(valid, cost, jnp.inf))
            gamma = 2.0 / (t + 2.0)
            ys = ((gap_of(split, target, cost, demand).astype(dtype),
                   _max_util(rho, num_links).astype(dtype),
                   gamma.astype(dtype)) if trace else ())
            return (1 - gamma) * split + gamma * target, ys

        return jax.lax.scan(body, split0, t0 + jnp.arange(iters, dtype=dtype))

    # exact line search on gamma in [0, 1]: a short bisection brackets the
    # root of the monotone derivative, then a few false-position (secant
    # within the bracket) steps polish it.  Every derivative evaluation is
    # an O(E) pass, and at scale (PF(79): E ~ 5e5 directed links) the
    # search rivals the [F, K, L] cost gather itself, so the eval count is
    # the budget that matters: 2+10+3 evals here beat the former
    # 20-halving search on cost AND on accuracy where it counts --
    # above-cap links make the derivative piecewise *linear* in gamma, and
    # secant interpolation is exact on linear pieces where pure bisection
    # (or Newton, whose curvature estimate explodes at the cap) stalls at
    # bracket resolution, which is what let infeasible probes stall with
    # capped-slope-sized gaps.  fp64 certification chases much smaller
    # gaps; it digs a deeper bracket first.
    ls_halvings = 20 if jnp.dtype(dtype) == jnp.float64 else 10

    @jax.named_scope("fluid.line_search")
    def _line_search(rho, drho):
        """argmin_gamma Phi(rho + gamma * drho) over [0, 1]: bisection +
        false-position polish on the monotone derivative
        d Phi/d gamma = <drho, 1 + w(rho + g*drho)> (Phi is convex along
        the segment; `drho` is a descent direction whenever the duality
        gap is positive)."""
        def dphi(g):
            return (drho * (1.0 + _queue_delay(rho + g * drho))).sum()

        def interp(lo, dlo, hi, dhi):
            denom = dhi - dlo
            g = jnp.where(denom > 0, lo - dlo * (hi - lo) / denom,
                          0.5 * (lo + hi))
            return jnp.clip(g, lo, hi)

        def shrink(carry, g):
            lo, dlo, hi, dhi = carry
            dg = dphi(g)
            pos = dg > 0
            return (jnp.where(pos, lo, g), jnp.where(pos, dlo, dg),
                    jnp.where(pos, g, hi), jnp.where(pos, dg, dhi))

        def half(carry, _):
            lo, dlo, hi, dhi = carry
            return shrink(carry, 0.5 * (lo + hi)), None

        def polish(carry, _):
            lo, dlo, hi, dhi = carry
            return shrink(carry, interp(lo, dlo, hi, dhi)), None

        zero, one = jnp.zeros((), dtype), jnp.ones((), dtype)
        d1 = dphi(one)
        carry = (zero, dphi(zero), one, d1)
        carry, _ = jax.lax.scan(half, carry, None, length=ls_halvings)
        carry, _ = jax.lax.scan(polish, carry, None, length=3)
        return jnp.where(d1 <= 0, one, interp(*carry))

    def cert_equilibrate(split0, demand, max_iters: int, util_tol,
                         t0: float = 0.0, decide_at=None,
                         trace_cap: int = 0):
        def trace_single(gap, rho, mu_lb, mu_ub):
            # one-sample trace for runs that never enter the loop
            if not trace_cap:
                return ()
            nan = jnp.full((trace_cap,), jnp.nan, dtype)
            return (jnp.zeros((trace_cap,), jnp.int32),
                    nan.at[0].set(gap.astype(dtype)),
                    nan.at[0].set(_max_util(rho, num_links).astype(dtype)),
                    nan.at[0].set(mu_lb.astype(dtype)),
                    nan.at[0].set(mu_ub.astype(dtype)),
                    nan.at[0].set(jnp.zeros((), dtype)),
                    jnp.ones((), jnp.int32))

        rho0 = loads(split0, demand)
        if mode not in ("ugal", "ugal_pf"):
            mu0 = _max_util(rho0, num_links).astype(dtype)
            z = jnp.zeros((), dtype)
            return (split0, rho0, z, mu0, mu0,
                    jnp.zeros((), jnp.int32), jnp.ones((), bool),
                    trace_single(z, rho0, mu0, mu0))

        @jax.named_scope("fluid.certify")
        def residual(split, rho):
            cost = cost_of(rho)
            target = target_of(split, rho, jnp.where(valid, cost, jnp.inf))
            return gap_of(split, target, cost, demand)

        def step_ugal(carry, _):
            # conjugate Frank-Wolfe (Mitradjieva-Lindberg CFW): combine the
            # previous combined target with the fresh best response so that
            # successive search directions are conjugate w.r.t. the diagonal
            # Beckmann Hessian in load space, then take an exact line-search
            # step -- vanilla FW's O(1/t) zigzag stalls the gap around 1 on
            # PF(13) at budgets where CFW is already at certification level
            split, rho, sbar, rbar, _g = carry
            cost = cost_of(rho)
            target = target_of(split, rho, jnp.where(valid, cost, jnp.inf))
            rho_t = loads(target, demand)
            h = _queue_delay_prime(rho)
            a = rbar - rho
            b = rho_t - rho
            bha = (b * h * a).sum()
            aha = (a * h * a).sum()
            beta = bha / (bha - aha)
            beta = jnp.clip(jnp.where(jnp.isfinite(beta), beta, 0.0),
                            0.0, 0.999)
            r_comb = beta * rbar + (1 - beta) * rho_t
            # keep it a descent direction; plain FW direction otherwise
            desc = ((r_comb - rho) * (1.0 + _queue_delay(rho))).sum() < 0
            beta = jnp.where(desc, beta, 0.0)
            s_comb = beta * sbar + (1 - beta) * target
            r_comb = beta * rbar + (1 - beta) * rho_t
            gamma = _line_search(rho, r_comb - rho)
            # loads are linear in the split, so rho tracks incrementally
            return (split + gamma * (s_comb - split),
                    rho + gamma * (r_comb - rho), s_comb, r_comb,
                    gamma.astype(dtype)), None

        def step_pf(carry, i):
            # UGAL_PF's gated target is not a linear-minimization oracle
            # (the residual can be negative), so line search on the
            # potential is meaningless: keep the harmonic schedule -- the
            # exact per-step math of the uncertified engines -- and let the
            # residual be the stopping/early-exit signal
            split, rho, sbar, rbar, _g = carry
            target = fw_target(split, rho)
            gamma = 2.0 / (i + 2.0)
            return (split + gamma * (target - split),
                    rho + gamma * (loads(target, demand) - rho),
                    sbar, rbar, gamma.astype(dtype)), None

        step = step_ugal if mode == "ugal" else step_pf

        traversals = (demand * lmax.astype(dtype)).sum()

        @jax.named_scope("fluid.certify")
        def done_of(gap, rho):
            # abs: the gated-residual mode's gap can go negative
            resid = jnp.abs(gap)
            mu_lb, mu_ub = _util_interval(rho, resid, num_links)
            # Phi(rho) - gap lower-bounds Phi*; the mass bound turns that
            # into the infeasible-side certificate the Bregman bracket
            # cannot provide (see _phi_mass_lower_bound)
            phi = (rho + _w_integral(rho)).sum()
            mu_lb = jnp.maximum(
                mu_lb, _phi_mass_lower_bound(phi - resid, traversals))
            done = (mu_ub - mu_lb) <= util_tol
            if decide_at is not None:
                done = done | (mu_ub <= decide_at) | (mu_lb > decide_at)
            return mu_lb, mu_ub, done

        def trace_init():
            if not trace_cap:
                return ()
            nan = jnp.full((trace_cap,), jnp.nan, dtype)
            return (jnp.zeros((trace_cap,), jnp.int32), nan, nan, nan, nan,
                    nan, jnp.zeros((), jnp.int32))

        def trace_rec(tr, t_next, gap, rho, mu_lb, mu_ub, glast):
            # samples land in fixed-size buffers via .at[idx].set -- no
            # host syncs, no dynamic shapes; the valid prefix length rides
            # along as `cnt` and the host trims after the jit returns
            if not trace_cap:
                return tr
            titer, tgap, tmu, tlb, tub, tgm, cnt = tr
            idx = jnp.minimum(cnt, trace_cap - 1)
            return (titer.at[idx].set(t_next),
                    tgap.at[idx].set(gap.astype(dtype)),
                    tmu.at[idx].set(_max_util(rho, num_links).astype(dtype)),
                    tlb.at[idx].set(mu_lb.astype(dtype)),
                    tub.at[idx].set(mu_ub.astype(dtype)),
                    tgm.at[idx].set(glast.astype(dtype)),
                    cnt + 1)

        def body(carry):
            state, _gap, _brk, t, _done, tr = carry
            state, _ = jax.lax.scan(
                step, state,
                t0 + t.astype(dtype) + jnp.arange(_CERT_STRIDE, dtype=dtype))
            split, _rho_inc, sbar, rbar, glast = state
            rho = loads(split, demand)  # shed incremental-update rounding
            gap = residual(split, rho)
            mu_lb, mu_ub, done = done_of(gap, rho)
            tr = trace_rec(tr, t + _CERT_STRIDE, gap, rho, mu_lb, mu_ub,
                           glast)
            return ((split, rho, sbar, rbar, glast), gap, (mu_lb, mu_ub),
                    t + _CERT_STRIDE, done, tr)

        def cond(carry):
            return (~carry[4]) & (carry[3] < max_iters)

        gap0 = residual(split0, rho0)
        lb0, ub0, done0 = done_of(gap0, rho0)
        tr0 = trace_rec(trace_init(), jnp.zeros((), jnp.int32), gap0, rho0,
                        lb0, ub0, jnp.zeros((), dtype))
        # sbar = split0 makes the first conjugate combination degenerate
        # (a = 0 -> beta guarded to 0), i.e. a plain FW first step
        carry = ((split0, rho0, split0, rho0, jnp.zeros((), dtype)),
                 gap0, (lb0, ub0), jnp.zeros((), jnp.int32), done0, tr0)
        out = jax.lax.while_loop(cond, body, carry)
        (split, rho, _sb, _rb, _g), gap, (mu_lb, mu_ub), t, done, tr = out
        return split, rho, gap, mu_lb, mu_ub, t, done, tr

    init = minvec if mode in ("min", "ugal", "ugal_pf") else uniform
    return _FWPieces(init, equilibrate, loads, cost_of, fw_target,
                     target_of, gap_of, cert_equilibrate)


def _mxu_factors(slot_ids, num_links: int):
    """One-hot factors of the slots' edge ids for `_mxu_link_loads`: id =
    `_MXU_LANES` * hi + lo.  `slot_ids` is [M] edge ids ("mxu": every
    path-link slot, M = F * K * L) or [T, S] ids within T tiles of
    ceil(n_hi / T) hi rows each ("mxu_tiles"); the factors are
    [..., rows] and [..., lanes] bfloat16 (0 and 1 are exact).  The pad id
    `num_links` falls in the tail the loads slice off."""
    n_hi = -(-(num_links + 1) // _MXU_LANES)
    tiles = slot_ids.shape[0] if slot_ids.ndim == 2 else 1
    return (jax.nn.one_hot(slot_ids // _MXU_LANES, -(-n_hi // tiles),
                           dtype=jnp.bfloat16),
            jax.nn.one_hot(slot_ids % _MXU_LANES, _MXU_LANES,
                           dtype=jnp.bfloat16))


def _bf16_head(x):
    """float32 `x` cut to its top 8 significand bits (sign, exponent, 7
    stored bits): exact in bfloat16.  A bit mask, not a rounding convert:
    XLA may keep a float32 -> bfloat16 -> float32 round trip in float32
    (excess precision), which would leave no remainder to split off."""
    bits = jax.lax.bitcast_convert_type(x, jnp.uint32) & jnp.uint32(0xFFFF0000)
    return jax.lax.bitcast_convert_type(bits, x.dtype)


def _bf16_parts(x):
    """[..., 3] bfloat16 parts of float32 `x` whose float32 sum is `x`
    exactly: the top 8 significand bits, the next 8, and what is left,
    which holds at most 8 (24 in all)."""
    h1 = _bf16_head(x)
    r1 = x - h1
    h2 = _bf16_head(r1)
    return jnp.stack([h1, h2, r1 - h2], axis=-1).astype(jnp.bfloat16)


def _mxu_link_loads(ws, hi_onehot, lo_onehot, num_links: int):
    """rho [E] from the slots' weights `ws` ([M], or [T, S] by tile) as MXU
    contractions, one per tile.

    rho[128 * hi + lo] = sum over slots with that edge id of their weight:
    one-hot(hi)^T [rows, S] times one-hot(lo) scaled by the weights,
    [S, lanes], with tile t holding hi rows [t * rows, (t + 1) * rows).
    Each float32 weight is three bfloat16 parts that add up to it exactly
    (`_bf16_parts`), so every product is exact; stacked as 3 * lanes
    columns and accumulated in float32, each edge's three part sums are
    added at the end, and rounding stays proportional to each edge's own
    load, as in the padded gather."""
    parts = _bf16_parts(ws)  # [..., S, 3]
    rhs = (parts[..., None] * lo_onehot[..., None, :]).reshape(
        *ws.shape, -1)
    c = ws.ndim - 1  # the slot axis; any axis before it is the tile's
    out = jax.lax.dot_general(hi_onehot, rhs,
                              (((c,), (c,)), (tuple(range(c)),) * 2),
                              preferred_element_type=jnp.float32)
    out = out.reshape(-1, 3, _MXU_LANES)
    rho = (out[:, 0] + out[:, 1]) + out[:, 2]
    return rho.reshape(-1)[:num_links]


def _max_util(rho, num_links: int):
    return jnp.max(rho) if num_links else jnp.zeros((), jnp.float32)


def _metrics(split, rho, cost, valid, hops, demand, offered, num_links: int):
    """In-jit FluidResult fields: (accepted, max_util, mean_latency,
    mean_hops) of one equilibrium, certified or not."""
    max_util = _max_util(rho, num_links)
    d = demand * offered
    dsum = jnp.maximum(d.sum(), _EPS)
    wsum = (split * jnp.where(valid, cost, 0.0)).sum(axis=1)
    lat = (d * wsum).sum() / dsum
    hop = (d * (split * hops).sum(axis=1)).sum() / dsum
    accepted = offered * jnp.minimum(1.0, 1.0 / jnp.maximum(max_util, _EPS))
    return accepted, max_util, lat, hop


@functools.partial(jax.jit,
                   static_argnames=("loads_kind", "num_links", "mode",
                                    "iters", "trace"))
def _solve_batch(eidx, loads_arrays, loads_kind, valid, is_min, first_edge,
                 demand, hops, num_links: int, mode: str, offered_vec,
                 iters: int = 250, trace: bool = False):
    """vmap of the cold-start equilibrium over a vector of offered loads:
    (accepted, max_util, mean_latency, mean_hops, ys), each batched over
    loads, with `ys` the per-step (gap, max_util, gamma) samples when
    `trace` and `()` without."""
    fw = _fw_pieces(
        eidx, loads_arrays, loads_kind, valid, is_min, first_edge,
        num_links, mode)

    def one(offered):
        d = demand * offered
        split, ys = fw.equilibrate(fw.init, d, iters, trace=trace)
        rho = fw.loads(split, d)
        return _metrics(split, rho, fw.cost_of(rho), valid, hops, demand,
                        offered, num_links) + (ys,)

    return jax.vmap(one)(offered_vec)


def _probe_schedule(iters: int, probes: int) -> tuple:
    """Per-probe Frank-Wolfe step budgets for the warm-started bisection.

    The first probe jumps half the load range away from the carried
    equilibrium and gets iters/2 steps to re-converge; the next four move
    geometrically less and start warm, so iters/4 suffices; probes beyond
    the fifth refine within 1/64 of the range from an almost-converged
    split and get iters/8.  Total probe work for the default tol=0.005
    (8 probes) is 1.875 * iters versus the scalar engine's 8 * iters.
    """
    sched = ([max(1, iters // 2)] + [max(1, iters // 4)] * 4
             + [max(1, iters // 8)] * max(0, probes - 5))
    return tuple(sched[:probes])


@functools.partial(jax.jit,
                   static_argnames=("loads_kind", "num_links", "mode",
                                    "iters", "probe_schedule", "trace"))
def _saturation_batch(eidx, loads_arrays, loads_kind, valid, is_min,
                      first_edge, demand, num_links: int, mode: str,
                      iters: int, probe_schedule: tuple, trace: bool = False):
    """In-jit saturation bisection with warm-started Frank-Wolfe probes.

    Probe sequence mirrors the scalar engine: a fully converged solve at
    offered = 1.0 (early accept when feasible), then one bisection step per
    `probe_schedule` entry over [0, 1].  Each probe re-equilibrates from
    the previous probe's split with that entry's step count, resuming the
    step-size schedule at `_WARM_T0` (the probes are unrolled, so each gets
    its own static trip count).

    Returns (sat, traces, brackets).  With `trace`, `traces` is one
    (gap, max_util, gamma) tuple per probe (probe lengths follow
    `probe_schedule`, so they stay a Python tuple rather than a stacked
    array) and `brackets` is [probes + 1, 4] rows (offered, feasible, lo,
    hi) after each probe; both are `()` without.
    """
    fw = _fw_pieces(
        eidx, loads_arrays, loads_kind, valid, is_min, first_edge,
        num_links, mode)
    split, ys = fw.equilibrate(fw.init, demand, iters, trace=trace)
    max1 = _max_util(fw.loads(split, demand), num_links)  # offered = 1.0

    one = jnp.ones((), jnp.float32)
    lo, hi = jnp.zeros((), jnp.float32), one
    yss = [ys]
    brs = [(one, (max1 <= 1.0).astype(jnp.float32), lo, hi)]
    for steps in probe_schedule:
        mid = 0.5 * (lo + hi)
        d = demand * mid
        split, ys = fw.equilibrate(split, d, steps, t0=_WARM_T0,
                                   trace=trace)
        feasible = _max_util(fw.loads(split, d), num_links) <= 1.0
        lo = jnp.where(feasible, mid, lo)
        hi = jnp.where(feasible, hi, mid)
        yss.append(ys)
        brs.append((mid, feasible.astype(jnp.float32), lo, hi))
    sat = jnp.where(max1 <= 1.0, one, lo)
    if not trace:
        return sat, (), ()
    return sat, tuple(yss), jnp.stack([jnp.stack(b) for b in brs])


@functools.partial(jax.jit,
                   static_argnames=("loads_kind", "num_links", "mode",
                                    "iters"))
def _truncation_gap(eidx, loads_arrays, loads_kind, valid, is_min, first_edge,
                    demand, num_links: int, mode: str, offered, iters: int):
    """L-inf gap between last-iterate and averaged Frank-Wolfe link loads
    after `iters` steps from the cold-start split at `offered` load (the
    estimated truncation error reported by `saturation_throughput`)."""
    fw = _fw_pieces(
        eidx, loads_arrays, loads_kind, valid, is_min, first_edge,
        num_links, mode)
    d = demand * offered

    def body(carry, t):
        split, acc = carry
        rho = fw.loads(split, d)
        gamma = 2.0 / (t + 2.0)
        return ((1 - gamma) * split + gamma * fw.fw_target(split, rho),
                acc + rho), None

    (split, acc), _ = jax.lax.scan(
        body, (fw.init, jnp.zeros(num_links, jnp.float32)),
        jnp.arange(iters, dtype=jnp.float32))
    return jnp.max(jnp.abs(fw.loads(split, d) - acc / iters))


@functools.partial(jax.jit,
                   static_argnames=("loads_kind", "num_links", "mode",
                                    "max_iters", "dtype", "trace_cap"))
def _certified_batch(eidx, loads_arrays, loads_kind, valid, is_min,
                     first_edge, demand, hops, num_links: int, mode: str,
                     offered_vec, util_tol, max_iters: int, dtype: str,
                     trace_cap: int = 0):
    """vmap of the certified equilibrium over a vector of offered loads:
    metrics + (gap, mu_lb, mu_ub, iters, converged, trace), each batched
    over loads."""
    dt = jnp.dtype(dtype)
    fw = _fw_pieces(eidx, loads_arrays, loads_kind, valid, is_min,
                    first_edge, num_links, mode, dtype=dt)
    dbase = demand.astype(dt)

    def one(offered):
        d = dbase * offered
        split, rho, gap, mu_lb, mu_ub, iters, ok, tr = fw.cert_equilibrate(
            fw.init, d, max_iters, util_tol, trace_cap=trace_cap)
        m = _metrics(split, rho, fw.cost_of(rho), valid, hops, dbase,
                     offered, num_links)
        return m + (gap, mu_lb, mu_ub, iters, ok, tr)

    return jax.vmap(one)(offered_vec)


@functools.partial(jax.jit,
                   static_argnames=("loads_kind", "num_links", "mode",
                                    "max_iters", "probes", "dtype",
                                    "trace_cap"))
def _certified_saturation(eidx, loads_arrays, loads_kind, valid, is_min,
                          first_edge, demand, num_links: int, mode: str,
                          util_tol, max_iters: int, probes: int, dtype: str,
                          trace_cap: int = 0):
    """In-jit certified saturation bisection with gap early-exit probes.

    Probe sequence mirrors `_saturation_batch` (offered = 1.0 first, then
    `probes` bisection steps over [0, 1], each warm-started from the
    previous probe's split at `_WARM_T0`), except that once a probe has
    been judged feasible every later probe starts from the split of the
    last feasible one: an infeasible probe's iterate piles load past the
    delay cap, where the cost is flat, and a lower load started there can
    still read max utilization > 1 when its budget runs out although its
    equilibrium is feasible (PF(79) UGAL lost a whole bisection cell that
    way).  Every probe runs
    `cert_equilibrate` with `decide_at=1.0`: it stops as soon as the gap's
    per-link utilization bracket certifies the probe's feasibility either
    way -- the uncertified engine's fixed per-probe budgets become
    data-dependent early exits.  Alongside the bisection's measured
    (lo, hi) it narrows a *certified* bracket: `lo_c` rises only on
    certified-feasible probes and `hi_c` falls only on certified-infeasible
    ones.

    Returns (sat, lo_c, hi_c, gap, mu_lb, mu_ub, total_iters,
    all_converged, traces, brackets) with gap / bracket from the final
    probe.  With `trace_cap > 0` the probes are traced: `traces` stacks
    each probe's `cert_equilibrate` sample buffers along a leading
    [probes + 1] axis (the probes are Python-unrolled, so stacking is
    free) and `brackets` is [probes + 1, 4] rows (offered, feasible,
    lo, hi) after each probe; both are `()` when tracing is off.
    """
    dt = jnp.dtype(dtype)
    fw = _fw_pieces(eidx, loads_arrays, loads_kind, valid, is_min,
                    first_edge, num_links, mode, dtype=dt)
    d1 = demand.astype(dt)
    split, rho, gap, mu_lb, mu_ub, it, ok, tr = fw.cert_equilibrate(
        fw.init, d1, max_iters, util_tol, decide_at=1.0,
        trace_cap=trace_cap)
    mu1 = _max_util(rho, num_links)
    total = it
    all_ok = ok

    one = jnp.ones((), dt)
    lo, hi = jnp.zeros((), dt), one
    lo_c = jnp.where(mu_ub <= 1.0, one, jnp.zeros((), dt))
    hi_c = one
    trs = [tr]
    brs = [(one, (mu1 <= 1.0).astype(dt), lo, hi)]
    warm, found = split, mu1 <= 1.0
    for _ in range(probes):
        mid = 0.5 * (lo + hi)
        dd = d1 * mid
        split, rho, gap, mu_lb, mu_ub, it, ok, tr = fw.cert_equilibrate(
            warm, dd, max_iters, util_tol, t0=_WARM_T0, decide_at=1.0,
            trace_cap=trace_cap)
        feasible = _max_util(rho, num_links) <= 1.0
        warm = jnp.where(feasible | ~found, split, warm)
        found = found | feasible
        lo = jnp.where(feasible, mid, lo)
        hi = jnp.where(feasible, hi, mid)
        lo_c = jnp.where(mu_ub <= 1.0, jnp.maximum(lo_c, mid), lo_c)
        hi_c = jnp.where(mu_lb > 1.0, jnp.minimum(hi_c, mid), hi_c)
        total = total + it
        all_ok = all_ok & ok
        trs.append(tr)
        brs.append((mid, feasible.astype(dt), lo, hi))
    sat = jnp.where(mu1 <= 1.0, one, lo)
    if trace_cap:
        traces = tuple(jnp.stack(parts) for parts in zip(*trs))
        brackets = jnp.stack([jnp.stack(b) for b in brs])
    else:
        traces, brackets = (), ()
    return (sat, lo_c, hi_c, gap, mu_lb, mu_ub, total, all_ok,
            traces, brackets)


def _cert_params(mode: str, util_tol, dtype, iters: int, cert_iters):
    """Resolve the certify=True knobs: (dtype, util_tol, max_iters, kind).
    fp64 certification is gated on JAX_ENABLE_X64 (the olmax test.sh
    idiom): with x64 enabled the default dtype is float64, without it
    requesting float64 raises instead of silently truncating, and the
    default `util_tol` tightens 0.05 -> 0.01 because fp64 can resolve the
    smaller duality gaps the tighter bracket needs (the fp32 gap's noise
    floor is an inner-product cancellation, ~1e-3 * total demand)."""
    x64 = bool(jax.config.jax_enable_x64)
    if dtype is None:
        dtype = "float64" if x64 else "float32"
    if dtype not in ("float32", "float64"):
        raise ValueError(f"unsupported certification dtype {dtype!r}")
    if dtype == "float64" and not x64:
        raise ValueError(
            "dtype='float64' certification needs JAX_ENABLE_X64=1 in the "
            "environment before jax is imported (see docs/benchmarks.md)")
    if util_tol is None:
        util_tol = 0.01 if dtype == "float64" else 0.05
    max_iters = int(cert_iters) if cert_iters is not None \
        else max(int(iters), 2000)
    kind = {"ugal": "duality-gap", "ugal_pf": "gated-residual"}.get(
        mode, "exact")
    return dtype, float(util_tol), max_iters, kind


def _certificate(gap, mu_lb, mu_ub, iters, ok, util_tol, dtype, kind):
    lb, ub = float(mu_lb), float(mu_ub)
    return Certificate(gap=float(gap), util_lb=lb, util_ub=ub,
                       util_err_bound=ub - lb, util_tol=util_tol,
                       iters=int(iters), dtype=dtype, converged=bool(ok),
                       kind=kind)


def _cert_trace(mode, kind, tr, brackets=None):
    """Host-side `ConvergenceTrace` from `cert_equilibrate` buffers.

    `tr` is one trace tuple (one load) or the stacked [P+1, cap]
    form from `_certified_saturation`; each probe's valid prefix is
    trimmed by its `cnt` and the iteration axis is made cumulative
    across probes.  Runs after the jit returns -- all syncs are here."""
    titer, tgap, tmu, tlb, tub, tgm, cnt = (np.asarray(x) for x in tr)
    if titer.ndim == 1:
        titer, tgap, tmu, tlb, tub, tgm = (
            a[None] for a in (titer, tgap, tmu, tlb, tub, tgm))
        cnt = np.asarray([cnt])
    rows = []
    offset = 0
    for p in range(titer.shape[0]):
        n = int(cnt[p])
        it = offset + titer[p, :n].astype(np.int64)
        rows.append((np.full(n, p, np.int64), it, tgap[p, :n], tmu[p, :n],
                     tlb[p, :n], tub[p, :n], tgm[p, :n]))
        if n:
            offset = int(it[-1])
    probe, iters, gap, mu, lb, ub, gm = (
        np.concatenate(cols) for cols in zip(*rows))
    br = np.asarray(brackets, np.float64) if brackets is not None \
        else np.zeros((0, 4))
    return ConvergenceTrace(mode=mode, kind=kind, stride=_CERT_STRIDE,
                            iters=iters, gap=gap, max_util=mu, util_lb=lb,
                            util_ub=ub, step_size=gm, probe=probe,
                            brackets=br)


def _fw_trace(mode, yss, brackets=None):
    """Host-side `ConvergenceTrace` from traced `equilibrate` outputs
    (one (gap, max_util, gamma) tuple per probe; stride-1 samples, NaN
    certified bounds -- these runs carry no certificate)."""
    rows = []
    offset = 0
    for p, ys in enumerate(yss):
        gap, mu, gm = (np.asarray(a, np.float64) for a in ys)
        n = gap.shape[0]
        nan = np.full(n, np.nan)
        rows.append((np.full(n, p, np.int64),
                     offset + np.arange(n, dtype=np.int64),
                     gap, mu, nan, nan, gm))
        offset += n
    probe, iters, gap, mu, lb, ub, gm = (
        np.concatenate(cols) for cols in zip(*rows))
    br = np.asarray(brackets, np.float64) if brackets is not None \
        else np.zeros((0, 4))
    return ConvergenceTrace(mode=mode, kind="uncertified", stride=1,
                            iters=iters, gap=gap, max_util=mu, util_lb=lb,
                            util_ub=ub, step_size=gm, probe=probe,
                            brackets=br)


def _as_flow_paths(fp) -> FlowPaths:
    """Normalize the `fp` argument of every public entry point: a single
    FlowPaths passes through; a sequence of chunks (e.g. assembled one
    destination block or traffic shard at a time by the blocked path
    builder) is concatenated via `FlowPaths.concat`.  Callers issuing many
    solver calls should concatenate once themselves so the device-array
    cache persists across calls."""
    if isinstance(fp, FlowPaths):
        return fp
    if isinstance(fp, (list, tuple)):
        return FlowPaths.concat(fp)
    raise TypeError(f"expected FlowPaths or a sequence of them, got "
                    f"{type(fp).__name__}")


def _equilibria(fp: FlowPaths, loads: list, iters: int, certify: bool,
                util_tol, dtype, cert_iters, trace: bool, span: str,
                **attrs) -> list:
    """One result per offered load in `loads`, from one vmapped program
    (`_certified_batch` or `_solve_batch`) under the span `span`: the
    body of `latency_curve`, and of `evaluate_load` as a batch of one."""
    eidx, loads_rep, valid, is_min, first_edge, demand, hops = \
        fp.device_arrays()
    paths = (eidx, loads_rep[1:], loads_rep[0], valid, is_min, first_edge,
             demand, hops, fp.num_links, fp.mode)
    rec = get_recorder()
    if certify:
        dtype, util_tol, max_iters, kind = _cert_params(
            fp.mode, util_tol, dtype, iters, cert_iters)
        trace_cap = (max_iters // _CERT_STRIDE + 2) if trace else 0
        vec = jnp.asarray(np.asarray(loads, dtype=dtype))
        with rec.span(span, mode=fp.mode, certify=True, **attrs) as sp:
            acc, mx, lat, hop, gap, mu_lb, mu_ub, it, ok, tr = sp.sync(
                _certified_batch(*paths, vec, util_tol, max_iters, dtype,
                                 trace_cap))
        if trace:
            parts = [np.asarray(x) for x in tr]
            traces = [_cert_trace(fp.mode, kind,
                                  tuple(p[i] for p in parts))
                      for i in range(len(loads))]
        else:
            traces = [None] * len(loads)
        return [CertifiedResult(
                    value=FluidResult(offered=l, accepted=float(a),
                                      max_util=float(m),
                                      mean_latency=float(la),
                                      mean_hops=float(h)),
                    cert=_certificate(g, lb, ub, i, o, util_tol, dtype, kind),
                    trace=t)
                for l, a, m, la, h, g, lb, ub, i, o, t in zip(
                    loads, np.asarray(acc), np.asarray(mx), np.asarray(lat),
                    np.asarray(hop), np.asarray(gap), np.asarray(mu_lb),
                    np.asarray(mu_ub), np.asarray(it), np.asarray(ok),
                    traces)]
    vec = jnp.asarray(np.asarray(loads, dtype=np.float32))
    with rec.span(span, mode=fp.mode, **attrs) as sp:
        acc, mx, lat, hop, ys = sp.sync(
            _solve_batch(*paths, vec, iters, trace))
    if trace:
        g, mu, gm = (np.asarray(a) for a in ys)
        traces = [_fw_trace(fp.mode, [(g[i], mu[i], gm[i])])
                  for i in range(len(loads))]
    else:
        traces = [None] * len(loads)
    return [FluidResult(offered=l, accepted=float(a), max_util=float(m),
                        mean_latency=float(la), mean_hops=float(h), trace=t)
            for l, a, m, la, h, t in zip(loads, np.asarray(acc),
                                         np.asarray(mx), np.asarray(lat),
                                         np.asarray(hop), traces)]


def evaluate_load(fp, offered: float, iters: int = 250,
                  certify: bool = False, util_tol: float = None,
                  dtype: str = None, cert_iters: int = None,
                  trace: bool = False):
    """FluidResult at one offered load; with `certify=True`, a
    `CertifiedResult` wrapping the FluidResult whose certificate bounds the
    reported utilizations' distance from the exact equilibrium (gap-driven
    line-search Frank-Wolfe instead of a fixed `iters` budget; `cert_iters`
    caps the certified run, default max(iters, 2000)).  Runs the program
    of `latency_curve` as a batch of one, so `evaluate_load(fp, x)` equals
    `latency_curve(fp, [x])[0]`.

    With `trace=True` the result additionally carries a
    `repro.obs.trace.ConvergenceTrace` in its `trace` field: per-stride
    (certified) or per-iteration (uncertified) duality gap, step size
    and max utilization, carried out of jit as returned arrays -- the
    compiled solve stays sync-free."""
    offered = float(offered)
    return _equilibria(_as_flow_paths(fp), [offered], iters, certify,
                       util_tol, dtype, cert_iters, trace,
                       "fluid.evaluate_load", offered=offered)[0]


def saturation_throughput(fp, tol: float = 0.005,
                          iters: int = 250, engine: str = "batched",
                          certify: bool = False, util_tol: float = None,
                          dtype: str = None, cert_iters: int = None,
                          trace: bool = False):
    """Largest per-endpoint offered load with max link utilization <= 1
    (bisection; adaptive splits re-equilibrate at every probe).  `fp` is a
    FlowPaths or a sequence of FlowPaths chunks (concatenated on entry).

    engine="batched" (default) runs the whole bisection inside one jit with
    warm-started probes (`_probe_schedule` budgets); engine="scalar" is the
    per-probe reference.  `truncation_error(fp, sat, iters)` estimates an
    uncertified adaptive-mode answer's Frank-Wolfe truncation error.

    With `certify=True` the result is a `CertifiedResult`: the bisection
    runs gap-driven probes that early-exit on certified feasibility
    decisions (`_certified_saturation`), `value` is the saturation float
    and `[sat_lo, sat_hi]` the certified bracket.  `util_tol` / `dtype` /
    `cert_iters` are the certification knobs (`_cert_params`).

    With `trace=True` (batched or certified engines) the result carries a
    `ConvergenceTrace` covering every bisection probe -- per-probe gap /
    step-size / max-util samples plus a bracket row per probe -- and the
    uncertified return type becomes `SaturationResult`.
    """
    fp = _as_flow_paths(fp)
    rec = get_recorder()
    probes = max(1, int(np.ceil(np.log2(1.0 / tol))))
    eidx, loads_rep, valid, is_min, first_edge, demand, _ = fp.device_arrays()
    paths = (eidx, loads_rep[1:], loads_rep[0], valid, is_min, first_edge,
             demand, fp.num_links, fp.mode)
    if certify:
        dtype, util_tol, max_iters, kind = _cert_params(
            fp.mode, util_tol, dtype, iters, cert_iters)
        trace_cap = (max_iters // _CERT_STRIDE + 2) if trace else 0
        with rec.span("fluid.saturation_throughput", mode=fp.mode,
                      certify=True, probes=probes) as sp:
            sat, lo_c, hi_c, gap, mu_lb, mu_ub, total_it, ok, trs, brs = \
                sp.sync(_certified_saturation(
                    *paths, util_tol, max_iters, probes, dtype, trace_cap))
        return CertifiedResult(
            value=float(sat),
            cert=_certificate(gap, mu_lb, mu_ub, total_it, ok, util_tol,
                              dtype, kind),
            sat_lo=float(lo_c), sat_hi=float(hi_c),
            trace=_cert_trace(fp.mode, kind, trs, brs) if trace else None)
    if engine == "batched":
        with rec.span("fluid.saturation_throughput", mode=fp.mode,
                      probes=probes) as sp:
            sat, yss, brs = sp.sync(_saturation_batch(
                *paths, iters, _probe_schedule(iters, probes), trace))
        sat = float(sat)
    elif engine != "scalar":
        raise ValueError(f"unknown engine {engine!r}")
    elif trace:
        raise ValueError("trace=True needs engine='batched' or "
                         "certify=True (the scalar reference re-enters "
                         "jit per probe and returns no trace buffers)")
    elif evaluate_load(fp, 1.0, iters).max_util <= 1.0:
        sat = 1.0
    else:
        lo, hi = 0.0, 1.0
        while hi - lo > tol:
            mid = 0.5 * (lo + hi)
            if evaluate_load(fp, mid, iters).max_util <= 1.0:
                lo = mid
            else:
                hi = mid
        sat = lo
    if not trace:
        return sat
    return SaturationResult(saturation=sat, trace=_fw_trace(fp.mode, yss, brs))


def truncation_error(fp, offered: float, iters: int = 250) -> float:
    """Estimated adaptive-mode Frank-Wolfe truncation error at `offered`
    load: the L-inf gap between the last-iterate link loads and the running
    average of the visited iterates' link loads after a cold `iters`-step
    solve.  Both converge to the Wardrop equilibrium loads, so the gap
    shrinks as O(1/iters); a gap comparable to the bisection tolerance
    means `iters` is too low for the answer (see the module docstring's
    truncation-noise discussion).  0.0 for oblivious modes, whose splits
    are load-independent fixed points.  Costs one full equilibrium solve
    -- benchmarks that time the bisection itself should call this outside
    the timed section; `certify=True` bounds the error instead."""
    fp = _as_flow_paths(fp)
    if fp.mode not in ("ugal", "ugal_pf") or not fp.num_links or offered <= 0:
        return 0.0
    eidx, loads_rep, valid, is_min, first_edge, demand, _ = fp.device_arrays()
    return float(_truncation_gap(eidx, loads_rep[1:], loads_rep[0], valid,
                                 is_min, first_edge, demand, fp.num_links,
                                 fp.mode, float(offered), iters))


def latency_curve(fp, loads, iters: int = 250, engine: str = "batched",
                  certify: bool = False, util_tol: float = None,
                  dtype: str = None, cert_iters: int = None,
                  trace: bool = False):
    """FluidResult per offered load.  engine="batched" (default) evaluates
    every load in one compiled vmapped call; engine="scalar" dispatches
    `evaluate_load` per load (the reference).  `fp` may be a sequence of
    FlowPaths chunks (concatenated on entry).  With `certify=True`, one
    vmapped certified call returning a `CertifiedResult` per load (each
    wrapping its FluidResult, with a per-load certificate).  With
    `trace=True`, each result carries its own per-load
    `ConvergenceTrace` (the vmapped solve returns the batched sample
    buffers; they are split per load host-side)."""
    fp = _as_flow_paths(fp)
    loads = [float(l) for l in loads]
    if certify or engine == "batched":
        return _equilibria(fp, loads, iters, certify, util_tol, dtype,
                           cert_iters, trace, "fluid.latency_curve",
                           points=len(loads))
    if engine != "scalar":
        raise ValueError(f"unknown engine {engine!r}")
    return [evaluate_load(fp, l, iters, trace=trace) for l in loads]
