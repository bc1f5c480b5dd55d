"""Pallas TPU kernel: blocked tropical (min,+) matrix product.

APSP over the PolarFly graph is O(N^3 log N) -- the hot spot of the §IX
structural sweeps (diameter under 100s of random link-failure draws).  The
MXU has no (min,+) mode, so this is a VPU kernel, but the data movement is
matmul-shaped: C tiles stay resident in VMEM while A-row / B-column tiles
stream from HBM, i.e. the same HBM->VMEM blocking as a matmul, with the
k-dimension innermost in the grid for accumulation.

Block shapes default to (128, 128, 128): 3 f32 tiles = 192 KiB << 16 MiB
VMEM, and 128 lanes align with the VPU (8, 128) vregs.

The fluid solver's per-candidate path costs (`ops.path_costs`) have no
kernel here.  They are a data-dependent gather from a per-link delay table
of E + 1 entries (505,601 at PF(79)), and the TPU compiler (Mosaic) only
lowers gathers whose table, indices and output share one tile shape: a
1-D table with a [bf, K, L] index tile is refused with "Only 2D gather is
supported", a [E / 128, 128] table with per-element row indices with
"Shape mismatch in input, indices and output".  XLA's own gather is the
one path on every backend.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _minplus_kernel(a_ref, b_ref, o_ref):
    """Grid (i, j, k); k innermost.  o[i,j] = min_k broadcast-min-plus."""
    @pl.when(pl.program_id(2) == 0)
    def _init():
        o_ref[...] = jnp.full_like(o_ref, 3.0e38 / 4)

    a = a_ref[...]  # [bm, bk]
    b = b_ref[...]  # [bk, bn]
    # [bm, bk, 1] + [1, bk, bn] -> min over k
    cand = jnp.min(a[:, :, None] + b[None, :, :], axis=1)
    o_ref[...] = jnp.minimum(o_ref[...], cand)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "interpret"))
def minplus_pallas(a: jnp.ndarray, b: jnp.ndarray, bm: int = 128,
                   bn: int = 128, bk: int = 128, interpret: bool = True):
    m, k = a.shape
    k2, n = b.shape
    assert k == k2
    # pad to block multiples with +inf (identity of min) / 0 is wrong: use INF
    inf = jnp.float32(3.0e38 / 4)
    mp = -(-m // bm) * bm
    np_ = -(-n // bn) * bn
    kp = -(-k // bk) * bk
    a = jnp.pad(a, ((0, mp - m), (0, kp - k)), constant_values=inf)
    b = jnp.pad(b, ((0, kp - k), (0, np_ - n)), constant_values=inf)
    grid = (mp // bm, np_ // bn, kp // bk)
    out = pl.pallas_call(
        _minplus_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, np_), jnp.float32),
        interpret=interpret,
    )(a.astype(jnp.float32), b.astype(jnp.float32))
    return out[:m, :n]
