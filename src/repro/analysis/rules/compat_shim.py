"""compat-shim: shard_map and mesh axis types route through one module each.

``repro/parallel/compat.py`` owns the one ``jax.shard_map`` call (with its
``check_vma`` setting) and ``repro/launch/mesh.py`` the one
``jax.make_mesh(axis_types=...)`` call; every other call site imports
from them, so a JAX upgrade that renames either API changes one file
instead of breaking call sites one by one.

Flags, outside the two shim files (excluded via the rule's scope config):

* ``from jax.experimental.shard_map import ...`` (and ``from
  jax.experimental import shard_map``);
* ``from jax import shard_map`` / ``jax.shard_map`` attribute uses;
* ``jax.sharding.AxisType`` imports or attribute uses.
"""

from __future__ import annotations

import ast
from typing import List

from ..report import Finding
from .base import FileContext, Rule

_MSG = ("shard_map / AxisType used directly; route through "
        "repro.parallel.compat / repro.launch.mesh so each API is called "
        "from one place")


def _flagged_import(node: ast.ImportFrom) -> bool:
    mod = node.module or ""
    if node.level:  # relative import (e.g. from .compat import shard_map)
        return False
    if mod == "jax.experimental.shard_map":
        return True
    names = {a.name for a in node.names}
    if mod == "jax.experimental" and "shard_map" in names:
        return True
    if mod == "jax.sharding" and "AxisType" in names:
        return True
    if mod == "jax" and "shard_map" in names:
        return True
    return False


class CompatShimRule(Rule):
    id = "compat-shim"
    description = ("shard_map/AxisType only via parallel/compat.py and "
                   "launch/mesh.py (one call site per API)")

    def check(self, ctx: FileContext) -> List[Finding]:
        out: List[Finding] = []
        for node in ast.walk(ctx.tree):
            if isinstance(node, ast.ImportFrom) and _flagged_import(node):
                out.append(self.finding(ctx, node, _MSG))
            elif isinstance(node, ast.Attribute):
                # only the outermost link of a dotted chain, so
                # jax.experimental.shard_map.shard_map reports once
                if isinstance(ctx.parent(node), ast.Attribute):
                    continue
                fq = ctx.dotted(node)
                if fq is None:
                    continue
                # prefix-match so jax.sharding.AxisType.Explicit (an access
                # THROUGH the flagged name) reports too
                flagged = ("jax.shard_map", "jax.sharding.AxisType",
                           "jax.experimental.shard_map")
                if any(fq == t or fq.startswith(t + ".") for t in flagged):
                    out.append(self.finding(ctx, node, _MSG))
        return out
