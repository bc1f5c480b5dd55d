"""host-sync: no host synchronization inside jit-compiled bodies.

``.item()``, ``float()``/``int()`` on a traced array, and ``np.asarray``
inside a jitted function either fail at trace time or -- worse -- silently
force a device->host transfer per call when the function falls back to
eager execution.  The solver keeps whole sweeps inside one jit (PR 2)
precisely to avoid such syncs.

Detection is a deliberate, documented approximation of
"@jax.jit-reachable": a function counts as jitted when decorated with
``@jax.jit`` or ``@functools.partial(jax.jit, ...)``, **or** when it is
jitted by assignment -- ``g = jax.jit(f)``, including through wrappers
whose first positional argument is the function, as in the blockwise
executor's ``mapped = jax.jit(shard_map(per_device, ...))`` -- and the
rule scans its whole body including nested defs (so the certified fluid
entry points -- ``_certified_batch`` / ``_certified_saturation`` and the
closures they trace -- are in scope: a ``float(gap)`` there is a per-call
device round-trip).  ``float()``/``int()`` are only flagged
when their argument mentions a *traced* parameter (not listed in
``static_argnames``) outside shape-like attribute accesses
(``x.shape`` / ``x.ndim`` / ``x.size`` / ``x.dtype`` and ``len(...)`` are
static under tracing and stay legal).
"""

from __future__ import annotations

import ast
from typing import List, Optional, Set, Tuple

from ..report import Finding
from .base import FileContext, Rule

_NP_HOST = {"numpy.asarray", "numpy.array"}
_STATIC_ATTRS = {"shape", "ndim", "size", "dtype"}


def _static_names(call: ast.Call) -> Set[str]:
    """String entries of a ``static_argnames=`` / ``static_argnums``-free
    keyword on a jit(...) or partial(jax.jit, ...) call."""
    for kw in call.keywords:
        if kw.arg == "static_argnames":
            v = kw.value
            if isinstance(v, ast.Constant) and isinstance(v.value, str):
                return {v.value}
            if isinstance(v, (ast.Tuple, ast.List)):
                return {e.value for e in v.elts
                        if isinstance(e, ast.Constant)
                        and isinstance(e.value, str)}
    return set()


def _jit_decoration(fn: ast.AST, ctx: FileContext
                    ) -> Optional[Tuple[bool, Set[str]]]:
    """(True, static_argnames) when `fn` is jit-decorated, else None."""
    for dec in fn.decorator_list:
        if ctx.dotted(dec) in ("jax.jit", "jit"):
            return True, set()
        if isinstance(dec, ast.Call):
            fq = ctx.dotted(dec.func)
            if fq in ("jax.jit", "jit"):
                return True, _static_names(dec)
            if (fq in ("functools.partial", "partial") and dec.args
                    and ctx.dotted(dec.args[0]) in ("jax.jit", "jit")):
                return True, _static_names(dec)
    return None


def _param_names(fn: ast.AST) -> Set[str]:
    a = fn.args
    return {p.arg for p in
            list(a.posonlyargs) + list(a.args) + list(a.kwonlyargs)}


def _jit_call_target(call: ast.Call, ctx: FileContext
                     ) -> Optional[Tuple[str, Set[str]]]:
    """(target function name, static_argnames) when `call` is the
    jit-by-assignment form ``jax.jit(f, ...)`` -- unwrapping wrapper calls
    whose first positional argument carries the function, so
    ``jax.jit(shard_map(per_device, mesh=...))`` resolves to
    ``per_device``."""
    if ctx.dotted(call.func) not in ("jax.jit", "jit") or not call.args:
        return None
    inner = call.args[0]
    while isinstance(inner, ast.Call) and inner.args:
        inner = inner.args[0]
    if isinstance(inner, ast.Name):
        return inner.id, _static_names(call)
    return None


def _mentions_traced(node: ast.AST, traced: Set[str]) -> bool:
    """True when the expression reads a traced name outside shape-like
    contexts.  Subtrees under ``.shape``-style attributes or ``len()``
    resolve to static Python values during tracing and are skipped."""
    if isinstance(node, ast.Attribute) and node.attr in _STATIC_ATTRS:
        return False
    if (isinstance(node, ast.Call) and isinstance(node.func, ast.Name)
            and node.func.id == "len"):
        return False
    if isinstance(node, ast.Name):
        return node.id in traced
    return any(_mentions_traced(c, traced)
               for c in ast.iter_child_nodes(node))


class HostSyncRule(Rule):
    id = "host-sync"
    description = ("no .item()/float()/int()-on-array/np.asarray inside "
                   "@jax.jit bodies -- host syncs break in-jit sweeps "
                   "(PR 2)")

    def check(self, ctx: FileContext) -> List[Finding]:
        out: List[Finding] = []
        fns = list(ctx.function_defs())
        # jitted by decorator...
        jitted = {}  # id(fn node) -> (fn, static names)
        by_name: dict = {}
        for fn in fns:
            by_name.setdefault(fn.name, fn)
            dec = _jit_decoration(fn, ctx)
            if dec is not None:
                jitted[id(fn)] = (fn, dec[1])
        # ...or by assignment anywhere in the file (g = jax.jit(f))
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Call):
                continue
            tgt = _jit_call_target(node, ctx)
            if tgt is not None and tgt[0] in by_name:
                fn = by_name[tgt[0]]
                prev = jitted.get(id(fn))
                jitted[id(fn)] = (fn, tgt[1] | (prev[1] if prev else set()))
        for fn, statics in jitted.values():
            traced = _param_names(fn) - statics
            for node in ast.walk(fn):
                if not isinstance(node, ast.Call):
                    continue
                if (isinstance(node.func, ast.Attribute)
                        and node.func.attr == "item" and not node.args):
                    out.append(self.finding(
                        ctx, node,
                        ".item() inside a jitted body forces a host sync"))
                elif ctx.dotted(node.func) in _NP_HOST:
                    out.append(self.finding(
                        ctx, node,
                        f"{ast.unparse(node.func)}() inside a jitted body "
                        "pulls the array to host; use jnp.asarray or move "
                        "it outside the jit"))
                elif (isinstance(node.func, ast.Name)
                        and node.func.id in ("float", "int")
                        and len(node.args) == 1
                        and _mentions_traced(node.args[0], traced)):
                    out.append(self.finding(
                        ctx, node,
                        f"{node.func.id}() on a traced value inside a "
                        "jitted body is a host sync (static_argnames "
                        "parameters and .shape reads are fine)"))
        return out
