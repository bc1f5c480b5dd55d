"""Plain reference for the fluid layer: the saturation load of the
Wardrop equilibrium of the M/D/1 link-delay routing game.

Model (the program's, as it documents it): a flow f of demand d_f
(flits/cycle at unit offered load) splits over its candidate paths; at
offered load lam a link carries rho_e = lam * sum of the demand routed
over it; a path costs the sum over its links of 1 + w(rho), with the M/D/1
wait w(r) = r / (2 (1 - r)) and r capped at 0.999.  The equilibrium
minimizes the Beckmann potential sum_e int_0^rho_e (1 + w).  Saturation
is the largest lam whose equilibrium keeps every rho_e <= 1.

The solver is path-based block coordinate descent: a group of flows moves
demand from each flow's costlier paths toward its cheapest one, in
proportion to the cost difference over the paths' curvature, and one
exact line search on the (convex) potential scales the group's step.  Arithmetic is
float64; `dtype="bfloat16"` rounds every stored array and every result to
bfloat16 instead, which is the precision control.
"""

from __future__ import annotations

import numpy as np

RHO_CAP = 0.999
W_CAP = RHO_CAP / (2.0 * (1.0 - RHO_CAP))


def _rounder(dtype: str):
    if dtype == "float64":
        return lambda a: np.asarray(a, np.float64)
    if dtype == "bfloat16":
        import ml_dtypes

        return lambda a: np.asarray(a, np.float64).astype(
            ml_dtypes.bfloat16).astype(np.float64)
    raise ValueError(f"unknown dtype {dtype!r}")


class Equilibrium:
    """Equilibria of one candidate-path set at any offered load."""

    def __init__(self, edges: np.ndarray, valid: np.ndarray,
                 demand: np.ndarray, num_links: int,
                 dtype: str = "float64"):
        self.E = int(num_links)
        self.e = np.where(edges >= 0, edges, self.E).astype(np.int64)
        self.real = edges >= 0
        self.valid = np.asarray(valid, bool)
        self.demand = np.asarray(demand, np.float64)
        self.r = _rounder(dtype)
        f, k, _ = edges.shape
        self.flat = self.e.reshape(f * k, -1)
        self.first = np.zeros((f, k))
        self.first[:, 0] = 1.0  # everything on the first (minimal) path

    def loads(self, x: np.ndarray, lam: float) -> np.ndarray:
        w = self.r(lam * self.demand[:, None] * x).reshape(-1)
        per = np.broadcast_to(w[:, None], self.flat.shape)
        return self.r(np.bincount(self.flat.ravel(), weights=per.ravel(),
                                  minlength=self.E + 1)[:self.E])

    def path_sum(self, per_link: np.ndarray) -> np.ndarray:
        table = np.append(per_link, 0.0)
        return self.r(table[self.e].sum(axis=-1))

    @staticmethod
    def delay(rho):
        rc = np.clip(rho, 0.0, RHO_CAP)
        return rc / (2.0 * (1.0 - rc))

    @staticmethod
    def delay_prime(rho):
        """w': 1 / (2 (1 - r)^2) below the cap, 0 above it (w is flat)."""
        rc = np.clip(rho, 0.0, RHO_CAP)
        return np.where(rho < RHO_CAP, 1.0 / (2.0 * (1.0 - rc) ** 2), 0.0)

    def _line_search(self, r, dr) -> float:
        """argmin over a in [0, 1] of the potential at r + a * dr (the
        links a step moves): safeguarded Newton on its monotone slope.
        Returns the bracket's low end, where the slope is still <= 0, so
        every step lowers the potential."""
        def slope(a):
            return float((dr * (1.0 + self.delay(r + a * dr))).sum())

        if slope(1.0) <= 0.0:
            return 1.0
        lo, hi, a = 0.0, 1.0, 0.0
        for _ in range(12):
            s = slope(a)
            if s > 0.0:
                hi = a
            else:
                lo = a
            curv = float((dr * dr * self.delay_prime(r + a * dr)).sum())
            nxt = a - s / curv if curv > 0 else 0.5 * (lo + hi)
            a = nxt if lo < nxt < hi else 0.5 * (lo + hi)
        return lo

    def gap(self, x, rho, lam) -> float:
        """Relative duality gap: demand-weighted excess of each flow's
        mean path cost over its cheapest path, over the total cost."""
        c = np.where(self.valid, self.path_sum(1.0 + self.delay(rho)), 0.0)
        cmin = np.where(self.valid, c, np.inf).min(axis=1)
        w = lam * self.demand[:, None] * x
        total = float((w * c).sum())
        return float((w * (c - cmin[:, None])).sum()) / total

    def solve(self, lam: float, x0: np.ndarray = None, max_sweeps: int = 60,
              rel_gap: float = 1e-8, blocks: int = 32):
        """(split, link loads, relative gap, sweeps) at `lam`.

        Block coordinate descent: each sweep visits the flows in `blocks`
        fixed groups; a group moves demand from each flow's costlier
        paths toward its cheapest one by a Newton step on the path costs,
        scaled by one exact line search over the links it touches."""
        x = self.r(self.first if x0 is None else x0).copy()
        d = self.demand
        groups = np.array_split(
            np.random.default_rng(0).permutation(len(d)), blocks)
        rel = float("nan")
        for sweep in range(max_sweeps):
            rho = self.loads(x, lam)
            rel = self.gap(x, rho, lam)
            if rel <= rel_gap:
                return x, rho, rel, sweep
            rho = np.append(rho, 0.0)  # slot E: the pad of short paths
            for fl in groups:
                e, real, ok = self.e[fl], self.real[fl], self.valid[fl]
                r = rho[e]
                c = np.where(ok, self.r((real * (1.0 + self.delay(r)))
                                        .sum(axis=-1)), np.inf)
                h = self.r((real * self.delay_prime(r)).sum(axis=-1))
                rows = np.arange(len(fl))
                kmin = np.argmin(c, axis=1)
                excess = np.where(ok, c - c[rows, kmin][:, None], 0.0)
                curv = lam * d[fl, None] * (h + h[rows, kmin][:, None])
                with np.errstate(divide="ignore", invalid="ignore"):
                    dx = -np.minimum(x[fl], np.where(curv > 0, excess / curv,
                                                     np.inf))
                dx = np.where(ok, dx, 0.0)
                dx[rows, kmin] = 0.0
                dx[rows, kmin] = -dx.sum(axis=1)
                w = (lam * d[fl, None] * dx)[:, :, None] * real
                links, inv = np.unique(e[real], return_inverse=True)
                drho = self.r(np.bincount(inv, weights=w[real]))
                a = self._line_search(rho[links], drho)
                x[fl] = self.r(np.maximum(x[fl] + a * dx, 0.0))
                rho[links] = self.r(rho[links] + a * drho)
        rho = self.loads(x, lam)
        return x, rho, self.gap(x, rho, lam), max_sweeps

    def feasible(self, lam: float, x0=None, chunk: int = 20,
                 max_chunks: int = 6):
        """(feasible, split): sweeps in chunks from `x0` until the max
        link load is 1 or below (feasible) or the budget runs out with it
        above 1 (infeasible: past saturation the load piles onto links
        beyond the delay cap instead of spreading)."""
        x = x0
        for _ in range(max_chunks):
            x, rho, rel, _ = self.solve(lam, x, chunk)
            if float(rho.max()) <= 1.0:
                return True, x
            if rel <= 1e-8:
                break
        return False, x

    def saturation(self, step: float = 1.0 / 32, refine: int = 5):
        """(lo, hi) around the saturation load: continuation upward from
        everything on the minimal path, in `step`s, each load warm-started
        from the last feasible one, then `refine` bisection steps inside
        the first infeasible step.  A set of single-path flows has its
        exact value 1 / (max link load at unit offered load)."""
        if (self.valid.sum(axis=1) == 1).all():
            mu1 = float(self.loads(self.first, 1.0).max())
            sat = min(1.0, 1.0 / mu1)
            return sat, sat
        lo, x_lo = 0.0, None
        hi = step
        while True:
            ok, x = self.feasible(hi, x_lo)
            if not ok:
                break
            lo, x_lo = hi, x
            if hi >= 1.0:
                return 1.0, 1.0
            hi = min(1.0, hi + step)
        for _ in range(refine):
            mid = 0.5 * (lo + hi)
            ok, x = self.feasible(mid, x_lo)
            if ok:
                lo, x_lo = mid, x
            else:
                hi = mid
        return lo, hi
