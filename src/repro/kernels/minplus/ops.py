"""Public ops for tropical matmul / APSP and the fluid solver's path costs."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from .kernel import minplus_pallas
from .ref import adjacency_to_dist0, minplus_ref, INF


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def path_costs(delay: jnp.ndarray, eidx: jnp.ndarray) -> jnp.ndarray:
    """[F, K] per-candidate path costs: ``sum_l delay[eidx[f, k, l]]``.

    The fluid solver's per-iteration best-response reduction (tropical:
    sum over links here, min over candidates in the caller).  One XLA
    gather-and-sum on every backend: the TPU compiler cannot lower this
    gather as a Pallas kernel (see `kernel.py`).  `ref.path_costs_ref` is
    the plain per-hop reference it is checked against.  Traceable under
    jit/vmap.
    """
    # label the reduction in XLA profiles: this op runs inside every
    # Frank-Wolfe step, and the scope name makes it findable in a
    # jax.profiler capture
    with jax.named_scope("minplus.path_costs"):
        return delay[eidx].sum(axis=-1)


def minplus(a: jnp.ndarray, b: jnp.ndarray, use_pallas: bool = True,
            block: int = 128) -> jnp.ndarray:
    """Tropical product; Pallas kernel (interpret mode off-TPU) or jnp ref."""
    if use_pallas:
        return minplus_pallas(a, b, bm=block, bn=block, bk=block,
                              interpret=not _on_tpu())
    return minplus_ref(a, b)


def apsp(adj, use_pallas: bool = False, block: int = 128) -> np.ndarray:
    """All-pairs shortest path distances from a boolean adjacency matrix.

    Repeated tropical squaring: log2(n) products.  `use_pallas=False` uses
    the jnp reference (XLA) -- the right default on CPU, where interpret-mode
    Pallas is Python-speed; on TPU flip `use_pallas=True`.
    Unreachable pairs come back as +inf."""
    adj = jnp.asarray(adj, dtype=bool)
    d = adjacency_to_dist0(adj)
    n = int(adj.shape[0])
    steps = max(1, int(np.ceil(np.log2(max(n - 1, 2)))))
    for _ in range(steps):
        d = minplus(d, d, use_pallas=use_pallas, block=block)
    d = np.array(d)
    d[d >= float(INF) / 2] = np.inf
    return d


def diameter_from_adj(adj, use_pallas: bool = False) -> float:
    """Graph diameter (inf if disconnected) -- drop-in for §IX sweeps."""
    d = apsp(adj, use_pallas=use_pallas)
    return float(d.max())
