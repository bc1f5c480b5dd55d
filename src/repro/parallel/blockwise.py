"""Shared blockwise execution core for the streaming engines.

PRs 3-5 grew three structurally identical blocked loops: the
source-blocked BFS (`repro.core.routing.distance_blocks`), the
destination-blocked path builder (`repro.simulation.paths`,
``engine="blocked"``), and chunked fluid assembly
(`FlowPaths.concat` / `build_flow_paths_chunks`).  Each sizes a block
from a byte budget, loops over blocks in Python, does per-block array
work, and streams the results to a consumer.  This module owns that
pattern once:

* `BlockPlan` -- the block axis: total item count, items per block
  (sized via `block_size_for_budget`), and the device count the sharded
  backend pads block groups to.
* `run_blocks` -- the executor, with two backends that must agree
  bit-exactly (the same two-engine discipline as every other pairing in
  this repo):

    - ``backend="host"`` -- the reference: a sequential Python loop
      calling `host_fn(items_blk)` per block.
    - ``backend="sharded"`` -- `device_fn` (a JAX-traceable analogue of
      `host_fn`) runs on `plan.devices` devices at once via `shard_map`
      (through `repro.parallel.compat`, never imported from jax
      directly): each round stacks one block per device, pads short
      blocks by repeating their last item (rows are independent, and
      padded rows are dropped before yielding).  The jitted mapped
      function is cached across `run_blocks` calls (keyed on the caller's
      `device_fn` and the concrete device objects), so repeated runs with
      a stable `device_fn` -- the latency sweep calling the blocked path
      builder once per load, say -- compile exactly once.

  Both backends yield ``(items_blk, outputs)`` in block order, so
  consumers are backend-blind.

* `block_size_for_budget` / `peak_bytes` -- the one byte-accounting
  helper pair behind `bfs_block_size`/`bfs_peak_bytes`,
  `dest_block_size`/`dest_block_peak_bytes`, and
  `blocked_paths_peak_bytes` (previously three near-identical copies).

This module imports jax lazily (only when the sharded backend actually
runs), so the numpy-only core modules can depend on it without pulling
jax at import time.
"""

from __future__ import annotations

from collections import OrderedDict
from dataclasses import dataclass
from typing import Callable, Iterator, Optional, Sequence, Tuple

import numpy as np

# repro.obs is stdlib-only, so this keeps the no-jax-at-import property
from ..obs.record import get_recorder

__all__ = [
    "DEFAULT_BUDGET_BYTES",
    "BlockPlan",
    "plan_blocks",
    "block_size_for_budget",
    "peak_bytes",
    "available_devices",
    "run_blocks",
]

# Default transient working-set budget shared by every blocked engine
# (routing aliases this as its historical `_BFS_BUDGET_BYTES` name).
DEFAULT_BUDGET_BYTES = 512 * 2 ** 20


def block_size_for_budget(total: int, per_item_bytes: int,
                          budget_bytes: int = DEFAULT_BUDGET_BYTES) -> int:
    """Items per block so the transient working set fits `budget_bytes`.

    Always at least 1 (a single item is the floor every streaming engine
    can run at -- arbitrarily small budgets degrade throughput, never
    correctness) and never more than `total`.
    """
    return int(min(max(total, 1),
                   max(1, budget_bytes // max(per_item_bytes, 1))))


def peak_bytes(block: int, per_item_bytes: int,
               resident_bytes: int = 0) -> int:
    """Estimated peak bytes of a blocked run: one block's transient
    working set plus whatever stays resident across blocks (output
    tables, per-flow arrays; streaming consumers pass 0)."""
    return block * per_item_bytes + resident_bytes


@dataclass(frozen=True)
class BlockPlan:
    """The block axis of a blocked computation.

    `total` items split into ceil(total / block) blocks; every block has
    exactly `block` items except a possibly short tail.  The sharded
    backend runs `devices` blocks per round (padding the tail round by
    repeating its last block), so `devices` is the mesh width it targets
    -- the host backend ignores it.

    `per_item_bytes` is informational: `plan_blocks` carries the byte
    sizing through so `run_blocks` can report per-block working-set
    bytes (`peak_bytes`) on its obs spans; 0 means unknown (plans built
    directly from an explicit `block`).
    """

    total: int
    block: int
    devices: int = 1
    per_item_bytes: int = 0

    def __post_init__(self):
        if self.total < 0 or self.block < 1 or self.devices < 1:
            raise ValueError(
                f"invalid BlockPlan(total={self.total}, block={self.block}, "
                f"devices={self.devices})")

    @property
    def num_blocks(self) -> int:
        return -(-self.total // self.block) if self.total else 0

    @property
    def num_rounds(self) -> int:
        """Sharded-backend rounds: ceil(num_blocks / devices)."""
        return -(-self.num_blocks // self.devices)

    def bounds(self, i: int) -> Tuple[int, int]:
        """[lo, hi) item range of block i."""
        lo = i * self.block
        return lo, min(lo + self.block, self.total)


def plan_blocks(total: int, per_item_bytes: Optional[int] = None,
                budget_bytes: int = DEFAULT_BUDGET_BYTES,
                block: Optional[int] = None, devices: int = 1) -> BlockPlan:
    """Build a `BlockPlan`, sizing the block from a byte budget unless an
    explicit `block` is given (same precedence every blocked engine uses)."""
    if block is None:
        if per_item_bytes is None:
            raise ValueError("plan_blocks needs per_item_bytes or block")
        block = block_size_for_budget(total, per_item_bytes, budget_bytes)
    return BlockPlan(total=total, block=int(block), devices=int(devices),
                     per_item_bytes=int(per_item_bytes or 0))


def available_devices() -> int:
    """Visible jax device count.  On CPU the count follows
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N``."""
    import jax
    return len(jax.devices())


def _as_tuple(out) -> tuple:
    return out if isinstance(out, tuple) else (out,)


def _resolve_backend(backend: str, plan: BlockPlan, device_fn) -> str:
    if backend not in ("auto", "host", "sharded"):
        raise ValueError(f"unknown backend {backend!r}")
    if backend == "sharded":
        if device_fn is None:
            raise ValueError("backend='sharded' requires a device_fn")
        return "sharded"
    if backend == "host" or device_fn is None:
        return "host"
    # auto: shard only when a multi-device mesh was requested AND exists,
    # and there is more than one block to spread -- otherwise the host
    # loop is both the reference and the fastest option.
    if plan.devices > 1 and plan.num_blocks > 1 and available_devices() > 1:
        return "sharded"
    return "host"


def _run_host(items: np.ndarray, plan: BlockPlan,
              host_fn: Callable) -> Iterator[Tuple[np.ndarray, tuple, None]]:
    for i in range(plan.num_blocks):
        lo, hi = plan.bounds(i)
        blk = items[lo:hi]
        yield blk, _as_tuple(host_fn(blk)), None


# `jax.jit` keys its trace cache on the wrapped callable's identity, and
# `_run_sharded` used to build a fresh `shard_map` wrapper per call, so
# every `run_blocks` call retraced (and recompiled) the mapped function
# even for an identical plan.  This bounded LRU persists the jitted
# wrapper across calls, keyed on everything baked into the trace closure:
# the caller's `device_fn` and the concrete mesh devices.  Block width is
# deliberately NOT in the key -- it only changes the input shape, which
# jax.jit already keys on under the one cached wrapper.  Callers only
# benefit when they pass a stable `device_fn` object (a module-level
# function or a retained closure); a lambda rebuilt per call misses.
_MAPPED_CACHE: "OrderedDict[tuple, tuple]" = OrderedDict()
_MAPPED_CACHE_SIZE = 16


def _fn_label(fn: Callable) -> str:
    """Module-qualified name of a device function, closures included
    (``repro.core.routing._bfs_device_fn.fn``)."""
    name = getattr(fn, "__qualname__", type(fn).__name__)
    return f"{getattr(fn, '__module__', '?')}.{name.replace('.<locals>', '')}"


def _mapped_fn(device_fn: Callable, devices: tuple) -> tuple:
    """(jitted shard_map of `device_fn`, the input sharding that puts
    block row j on ``devices[j]``), from the cross-call cache."""
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    from .compat import shard_map

    key = (device_fn, devices)
    hit = _MAPPED_CACHE.get(key)
    if hit is not None:
        _MAPPED_CACHE.move_to_end(key)
        return hit
    # cache miss = a fresh shard_map wrapper = an XLA retrace on first
    # call; surfaced as a counter, labelled with the device function, so
    # sweeps that accidentally rebuild their device_fn per call show up
    # in the trace instead of just running mysteriously slow
    get_recorder().counter("blockwise.retrace", 1, devices=len(devices),
                           fn=_fn_label(device_fn))

    mesh = Mesh(np.asarray(devices), ("blocks",))
    spec = PartitionSpec("blocks")

    def _per_device(idx):  # [1, block] -> tuple of [1, block-leading] outputs
        return tuple(o[None] for o in _as_tuple(device_fn(idx[0])))

    mapped = jax.jit(shard_map(_per_device, mesh=mesh, in_specs=spec,
                               out_specs=spec))
    _MAPPED_CACHE[key] = (mapped, NamedSharding(mesh, spec))
    while len(_MAPPED_CACHE) > _MAPPED_CACHE_SIZE:
        _MAPPED_CACHE.popitem(last=False)
    return _MAPPED_CACHE[key]


def _run_sharded(items: np.ndarray, plan: BlockPlan,
                 device_fn: Callable) -> Iterator[Tuple[np.ndarray, tuple,
                                                        int]]:
    """One block per device per round; the mapped function comes from the
    cross-call `_MAPPED_CACHE` and block shapes are padded to a constant
    [devices, block], so a stable `device_fn` compiles exactly once.
    Each round's stacked blocks are placed row j on device j, and each
    yielded block carries the id of the device whose output shard it is."""
    import jax

    ndev = max(1, min(plan.devices, len(jax.devices())))
    mapped, sharding = _mapped_fn(device_fn, tuple(jax.devices()[:ndev]))

    for r in range(plan.num_rounds):
        first = r * ndev
        blocks = []
        for j in range(ndev):
            lo, hi = plan.bounds(min(first + j, plan.num_blocks - 1))
            blk = items[lo:hi]
            if len(blk) < plan.block:  # pad short tail: rows independent
                blk = np.concatenate(
                    [blk, np.repeat(blk[-1:], plan.block - len(blk))])
            blocks.append(blk)
        with get_recorder().span("blockwise.round", index=r):
            outs = mapped(jax.device_put(np.stack(blocks), sharding))
            owner = {s.index[0].start or 0: s.device.id
                     for s in outs[0].addressable_shards}
            outs = tuple(np.asarray(o) for o in outs)  # one host sync per round
        for j in range(min(ndev, plan.num_blocks - first)):
            lo, hi = plan.bounds(first + j)
            yield items[lo:hi], tuple(o[j, :hi - lo] for o in outs), owner[j]


def run_blocks(items: Sequence, plan: BlockPlan, host_fn: Callable,
               device_fn: Optional[Callable] = None,
               backend: str = "auto",
               progress: Optional[Callable[[int, int], None]] = None,
               ) -> Iterator[Tuple[np.ndarray, tuple]]:
    """Stream ``(items_blk, outputs)`` per block, in block order.

    `items` is the 1-D array being blocked (source ids, destination ids,
    flow indices, ...).  `host_fn(items_blk)` is the numpy reference; it
    may return a single value or a tuple (normalized to a tuple either
    way -- non-array returns such as FlowPaths chunks are passed through
    untouched by the host backend).  `device_fn` is its JAX-traceable
    twin operating on a full-size [block] index array, returning arrays
    with a leading block axis; rows must be independent, because the
    sharded backend pads short blocks by repeating rows and then drops
    the padded outputs.

    ``backend="auto"`` runs sharded only when `plan.devices > 1`, more
    than one device is actually visible, there is more than one block,
    and a `device_fn` exists; everything else falls back to the host
    loop, so single-device environments always take the reference path.

    Every block is wrapped in a ``blockwise.block`` obs span recording
    the resolved backend, block index, item count, (when the plan
    carries `per_item_bytes`) the block's working-set bytes, and (sharded
    backend) the id of the device that computed it.  The
    sharded backend computes a whole round of `devices` blocks at its
    first block's ``next()``, so that round's wall time lands on the
    round's first span -- per-round attribution, not per-block.
    `progress(done_blocks, num_blocks)` is called after each block is
    produced (before it is yielded), e.g. for long streaming sweeps that
    want a heartbeat without consuming the trace.
    """
    items = np.asarray(items)
    if plan.total != len(items):
        raise ValueError(f"plan.total={plan.total} != len(items)={len(items)}")
    if plan.total == 0:
        return
    resolved = _resolve_backend(backend, plan, device_fn)
    inner = (_run_host(items, plan, host_fn) if resolved == "host"
             else _run_sharded(items, plan, device_fn))
    rec = get_recorder()
    nblocks = plan.num_blocks
    for i in range(nblocks):
        with rec.span("blockwise.block", backend=resolved, index=i) as sp:
            blk, outs, device = next(inner)
            sp.set(items=len(blk))
            if device is not None:
                sp.set(device=device)
            if plan.per_item_bytes:
                sp.set(bytes=peak_bytes(len(blk), plan.per_item_bytes))
        if progress is not None:
            progress(i + 1, nblocks)
        yield blk, outs
