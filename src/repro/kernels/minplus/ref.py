"""Pure-jnp oracle for the tropical (min,+) matrix product and APSP."""

from __future__ import annotations

import jax.numpy as jnp

INF = jnp.float32(3.0e38) / 4  # headroom so inf+inf does not overflow


def minplus_ref(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """C[i, j] = min_k A[i, k] + B[k, j]; float32."""
    return jnp.min(a[:, None, :] + b.T[None, :, :], axis=-1)


def path_costs_ref(delay: jnp.ndarray, eidx: jnp.ndarray) -> jnp.ndarray:
    """Per-candidate path costs from a padded per-link delay table.

    ``delay`` is ``[E + 1]`` (last slot is the zero pad that -1-padded edge
    ids were remapped to); ``eidx`` is ``[F, K, L]`` int32.  Returns
    ``cost[f, k] = sum_l delay[eidx[f, k, l]]`` -- the (+)-half of the
    tropical best-response reduction the fluid solver runs per
    Frank-Wolfe iteration (the min-over-K half stays in the caller, which
    also needs the full ``[F, K]`` cost for the duality gap).

    The plain reference for `ops.path_costs`: one hop at a time, added
    left to right.  `path_costs` gathers the whole ``[F, K, L]`` block and
    reduces it, so a backend may sum the L terms in another order; the
    two agree to float32 rounding of an L-term sum of positive delays.
    """
    cost = delay[eidx[..., 0]]
    for hop in range(1, eidx.shape[-1]):
        cost = cost + delay[eidx[..., hop]]
    return cost


def adjacency_to_dist0(adj: jnp.ndarray) -> jnp.ndarray:
    """Boolean adjacency -> 1-step distance matrix (0 diag, 1 edge, INF else)."""
    n = adj.shape[0]
    d = jnp.where(adj, 1.0, INF).astype(jnp.float32)
    return jnp.where(jnp.eye(n, dtype=bool), 0.0, d)


def apsp_ref(adj: jnp.ndarray) -> jnp.ndarray:
    """All-pairs shortest paths by repeated tropical squaring (log2 n rounds)."""
    d = adjacency_to_dist0(adj)
    n = adj.shape[0]
    steps = max(1, int(jnp.ceil(jnp.log2(jnp.maximum(n - 1, 2)))))
    for _ in range(int(steps)):
        d = minplus_ref(d, d)
    return d
