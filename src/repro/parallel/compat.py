"""The one entry point for `shard_map` in this repo.

Call sites import `shard_map` from here, never from jax directly (the
reprolint `compat-shim` rule enforces it), so the replication-check
setting lives in one place.
"""

from __future__ import annotations

import jax

__all__ = ["shard_map"]


def shard_map(f, *, mesh, in_specs, out_specs):
    """`jax.shard_map` with replication checking off."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)
