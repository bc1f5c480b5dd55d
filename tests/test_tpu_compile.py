"""Main-path programs compile for a TPU v5e chip that is described, not
attached.

The TPU compiler ships with jax, so these run on a CPU-only machine: each
lowers a jitted program with shapes placed on a described ``v5e:2x2``
device and asks the chip's compiler for an executable.  A refusal (an
unsupported gather, too much memory) fails here instead of on the chip.
Nothing runs, so no result or time is checked.

The topology is described inside a module-scoped fixture, never at
import: only one process may load the TPU library, and under pytest-xdist
every worker imports this file.  All such compiles stay in this one file,
so one worker holds the library.
"""
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.compilation_cache import compilation_cache
from jax.sharding import Mesh, NamedSharding, PartitionSpec, \
    SingleDeviceSharding

from repro.core.polarfly import build_polarfly
from repro.core.routing import _dest_device_fn, dest_block_size
from repro.kernels.minplus.ops import path_costs
from repro.parallel.compat import shard_map
from repro.simulation import fluid, packet

# PF(79) random_perm UGAL with 10 Valiant candidates: 6,319 flows, K = 11
# candidates of at most L = 4 links, 505,600 directed links
PF79_F, PF79_K, PF79_L, PF79_E = 6319, 11, 4, 505_600


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # an executable for a described chip is written to the persistent
    # cache but cannot be read back without the chip: keep the cache off
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _on(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def test_path_costs_compiles_for_v5e_at_pf79(one_chip):
    """The per-iteration path-cost gather at PF(79) shapes, as XLA's own
    gather (no Pallas custom call) with next to no temporaries."""
    compiled = jax.jit(path_costs).lower(
        _on(one_chip, (PF79_E + 1,), jnp.float32),
        _on(one_chip, (PF79_F, PF79_K, PF79_L), jnp.int32)).compile()
    mem = compiled.memory_analysis()
    assert mem.output_size_in_bytes >= PF79_F * PF79_K * 4  # tile-padded
    assert mem.temp_size_in_bytes < 2 ** 24
    assert "tpu_custom_call" not in compiled.as_text()


def _certified_saturation(one_chip, kind, f, k, l, e, w, tiles=()):
    """`_certified_saturation` compiled for one chip: UGAL, 3 probes;
    `tiles` (T, S) gives ("mxu_tiles", ...)'s two [T, S] arrays."""
    args = (_on(one_chip, (f, k, l), jnp.int32),
            (_on(one_chip, (e, w), jnp.int32),
             *[_on(one_chip, tiles, jnp.int32)] * (2 if tiles else 0)),
            kind,
            _on(one_chip, (f, k), jnp.bool_),
            _on(one_chip, (f, k), jnp.bool_),
            _on(one_chip, (f,), jnp.int32),
            _on(one_chip, (f,), jnp.float32))
    return fluid._certified_saturation.lower(
        *args, e, "ugal", 0.05, 256, 3, "float32", 0).compile()


def test_certified_saturation_compiles_for_v5e(one_chip):
    """The certified UGAL bisection (conjugate Frank-Wolfe, line search,
    gap bracket) at PF(7)-like shapes, padded-incidence link loads."""
    compiled = _certified_saturation(one_chip, "pad", 56, 8, 4, 456, 12)
    assert compiled.memory_analysis().temp_size_in_bytes < 2 ** 30


@pytest.mark.parametrize("f,k,l,e,w", [
    (56, 8, 4, 456, 12),          # PF(7)-like, as above
    (993, 11, 4, 31_744, 9)])     # PF(31) UGAL, 1 minimal + 10 Valiant
def test_certified_saturation_mxu_loads_compile_for_v5e(one_chip, f, k, l,
                                                        e, w):
    """The same bisection with ("mxu", inc) link loads: XLA's own matmul
    under `fluid.loads` (no custom kernel), and no gather left there."""
    compiled = _certified_saturation(one_chip, "mxu", f, k, l, e, w)
    assert compiled.memory_analysis().temp_size_in_bytes < 2 ** 30
    text = compiled.as_text()
    assert "tpu_custom_call" not in text
    loads_ops = [ln for ln in text.splitlines() if "fluid.loads/" in ln]
    assert any("convolution(" in ln for ln in loads_ops)
    assert not any("gather(" in ln for ln in loads_ops)


def test_certified_saturation_mxu_tiles_compile_for_v5e_at_pf79(one_chip):
    """The bisection at `pf79_ugal.sat`'s shapes (F = N = 6,321 flows, one
    a router) with ("mxu_tiles", ...) loads, the tiles its deployment
    takes on the chip (23 of 11,686 slots): XLA's own matmuls under
    `fluid.loads`, within a tenth of the chip's memory."""
    compiled = _certified_saturation(one_chip, "mxu_tiles", 6321, PF79_K,
                                     PF79_L, PF79_E, 6, (23, 11_686))
    assert compiled.memory_analysis().temp_size_in_bytes < 1.6e9
    text = compiled.as_text()
    assert "tpu_custom_call" not in text
    assert any("convolution(" in ln for ln in text.splitlines()
               if "fluid.loads/" in ln)


def _whiles_in_loops(hlo: str) -> list:
    """The `while` ops of a compiled HLO module that the body of another
    `while` reaches, through any computation it calls."""
    comps = dict(re.findall(r"^(?:ENTRY )?%(\S+) [^\n]*\{\n(.*?)^\}$", hlo,
                            re.M | re.S))
    inner, seen = [], set()
    stack = re.findall(r"\bwhile\(.*?\bbody=%([\w.\-]+)", hlo)
    while stack:
        name = stack.pop()
        if name in seen or name not in comps:
            continue
        seen.add(name)
        inner += re.findall(r"%(\S+) = .*\bwhile\(", comps[name])
        stack += re.findall(r"%([\w.\-]+)", comps[name])
    return inner


def test_packet_scan_compiles_for_v5e(one_chip):
    """The packet engine's scan (sort-based arbitration, UGAL injection
    choice) at small shapes.  A cycle holds no loop of its own: a binary
    search (`jnp.searchsorted`'s default lowering) would be one."""
    f, k, l1, p, n, e = 56, 8, 5, 4000, 57, 456
    i32 = jnp.int32
    args = (_on(one_chip, (2, f, k, l1), i32), _on(one_chip, (2, f, k), i32),
            _on(one_chip, (2, f), i32), _on(one_chip, (p + 1,), i32),
            _on(one_chip, (p + 1,), i32), _on(one_chip, (2, p + 1), i32),
            _on(one_chip, (n + 1,), i32), _on(one_chip, (f, k), i32),
            _on(one_chip, (0,), i32), _on(one_chip, (), i32))
    compiled = packet._run_batched.lower(
        *args, e_num=e, size=4, capacity=32, adaptive=True, gated=False,
        seg0=100, seg1=0).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 2 ** 30
    text = compiled.as_text()
    assert "while(" in text  # the scan over cycles
    assert _whiles_in_loops(text) == []


def test_sharded_dest_columns_compile_for_v5e_2x2(topo, one_chip):
    """The blockwise sharded backend's destination-column BFS twin (int16
    distances) at PF(79), one block per chip of a 2x2 mesh: blocks are
    independent, so the program holds no collective."""
    g = build_polarfly(79).graph
    _, indices = g.csr
    block = dest_block_size(g.n, len(indices),
                            g.padded_neighbors[0].shape[1])
    fn = _dest_device_fn(g)
    mesh = Mesh(np.asarray(topo.devices), ("blocks",))
    spec = PartitionSpec("blocks")

    def per_device(idx):
        return tuple(o[None] for o in fn(idx[0]))

    compiled = jax.jit(shard_map(per_device, mesh=mesh, in_specs=spec,
                                 out_specs=spec)).lower(
        jax.ShapeDtypeStruct((len(topo.devices), block), jnp.int32,
                             sharding=NamedSharding(mesh, spec))).compile()
    text = compiled.as_text()
    assert not any(op in text for op in ("all-gather", "all-reduce",
                                         "collective-permute", "all-to-all"))
    assert compiled.memory_analysis().temp_size_in_bytes < 2 ** 30
