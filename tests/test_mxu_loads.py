"""Link loads as MXU contractions: one over every path-link slot (the
("mxu", inc) incidence kind), or one per tile of edge ids past the byte
budget (("mxu_tiles", inc, slot_fk, slot_ids)).

`FlowPaths.device_arrays` picks the kinds on TPU only, so every test here
adds the CPU to `paths._MXU_LOADS_PLATFORMS` and builds fresh paths; a
budget monkeypatched small forces several tiles at PF(7) and PF(13).  The
contractions must be as exact as the padded gather they replace: every
per-edge load within the float32 bound of a sum of that edge's own terms,
the same certified saturation bracket, float64 loads still gathered, and
each one-hot operand held to its byte budget.
"""
import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.polarfly import build_polarfly
from repro.core.routing import build_routing
from repro.simulation import (build_flow_paths, make_pattern,
                              saturation_throughput)
from repro.simulation import fluid
from repro.simulation import paths as paths_mod
from repro.simulation.traffic import TrafficPattern

_U = 2.0 ** -24  # float32 unit roundoff


@pytest.fixture
def mxu_on_cpu(monkeypatch):
    monkeypatch.setattr(paths_mod, "_MXU_LOADS_PLATFORMS",
                        paths_mod._MXU_LOADS_PLATFORMS + ("cpu",))


@functools.lru_cache(maxsize=None)
def _routing(q: int):
    pf = build_polarfly(q)
    return build_routing(pf.graph, pf)


def _perm_paths(q: int, mode: str):
    rt = _routing(q)
    pat = make_pattern("random_perm", rt, p=(q + 1) // 2, seed=0)
    kw = {} if mode == "min" else dict(k_candidates=6, seed=5)
    return build_flow_paths(rt, pat, mode, **kw)


def _hot_dst_paths():
    """Every router sends to router 0: the skewed incidence of
    `test_scatter_fallback.py`, whose pad width is far above the mean."""
    rt = _routing(7)
    src = np.arange(1, build_polarfly(7).graph.n, dtype=np.int32)
    pat = TrafficPattern("hot_dst", src, np.zeros(len(src), np.int32),
                         np.ones(len(src), np.float32),
                         endpoints_per_router=1)
    return build_flow_paths(rt, pat, "min")


def _loads(fp, kind, split):
    eidx, rep, valid, is_min, first_edge, demand, _ = fp.device_arrays()
    fw = fluid._fw_pieces(eidx, rep[1:], kind, valid, is_min, first_edge,
                          fp.num_links, fp.mode)
    return np.asarray(jax.jit(fw.loads)(split, demand), np.float64)


def _exact_loads(fp, split):
    """float64 per-edge sums, and each edge's number of terms."""
    w = np.asarray(split, np.float64) * fp.pattern.demand[:, None]
    e = fp.edges.reshape(-1)
    real = e >= 0
    wm = np.repeat(w.reshape(-1), fp.edges.shape[2])[real]
    rho = np.bincount(e[real], weights=wm, minlength=fp.num_links)
    return rho, np.bincount(e[real], minlength=fp.num_links)


@pytest.mark.parametrize("case", [
    (7, "min"), (7, "ugal"), (7, "ugal_pf"),
    (13, "min"), (13, "ugal"), (13, "ugal_pf"), "hot_dst"])
def test_mxu_loads_match_float64_as_closely_as_the_gather(mxu_on_cpu, case):
    fp = _hot_dst_paths() if case == "hot_dst" else _perm_paths(*case)
    assert fp.device_arrays()[1][0] == "mxu"
    rng = np.random.default_rng(3)
    # weights over six decades, so every bfloat16 part carries bits
    split = (rng.random(fp.valid.shape) ** 6 * fp.valid).astype(np.float32)
    ref, count = _exact_loads(fp, split)
    # a float32 sum of n positive terms is within (n - 1) u of its value;
    # the contraction adds its three part sums at the end: two more
    bound = (count + 2) * _U * ref
    errs = {}
    for kind in ("pad", "mxu"):
        rho = _loads(fp, kind, split)
        assert np.all(np.abs(rho - ref) <= bound), kind
        nz = ref > 0
        errs[kind] = np.max(np.abs(rho - ref)[nz] / ref[nz])
    assert errs["mxu"] <= max(2 * errs["pad"], 2 * _U), errs


def test_mxu_certified_saturation_brackets_like_the_gather(monkeypatch):
    """Same bracket, value and step count at a budget where every probe
    decides (a probe that runs out of budget ends wherever the last
    rounding left it, on either path)."""
    fp_pad = _perm_paths(13, "ugal")
    assert fp_pad.device_arrays()[1][0] == "pad"
    monkeypatch.setattr(paths_mod, "_MXU_LOADS_PLATFORMS", ("cpu",))
    fp_mxu = _perm_paths(13, "ugal")
    assert fp_mxu.device_arrays()[1][0] == "mxu"
    pad = saturation_throughput(fp_pad, tol=0.05, certify=True,
                                cert_iters=1024)
    mxu = saturation_throughput(fp_mxu, tol=0.05, certify=True,
                                cert_iters=1024)
    assert (mxu.sat_lo, mxu.sat_hi) == (pad.sat_lo, pad.sat_hi)
    assert mxu.value == pad.value
    assert mxu.cert.iters == pad.cert.iters


def _op_names(lowered) -> list:
    return re.findall(r'loc\("([^"]*)"', lowered.as_text(debug_info=True))


def test_float64_loads_take_the_gather(mxu_on_cpu):
    fp = _perm_paths(7, "ugal")
    eidx, rep, valid, is_min, first_edge, demand, _ = fp.device_arrays()
    assert rep[0] == "mxu"

    def loads_ops(dtype):
        names = _op_names(fluid._certified_saturation.lower(
            eidx, rep[1:], rep[0], valid, is_min, first_edge, demand,
            fp.num_links, "ugal", 0.05, 64, 2, dtype, 0))
        return [n.split("fluid.loads/", 1)[1] for n in names
                if "fluid.loads/" in n]

    f32 = loads_ops("float32")
    assert any("dot_general" in n for n in f32)
    assert not any("gather" in n for n in f32)
    with jax.enable_x64(True):
        f64 = loads_ops("float64")
        res = saturation_throughput(fp, tol=0.1, certify=True,
                                    dtype="float64", cert_iters=256)
    assert any("gather" in n for n in f64)
    assert not any("dot_general" in n for n in f64)
    assert res.cert.dtype == "float64" and res.sat_lo <= res.value


def _tile_onehot_bytes(rep, num_links):
    """Bytes of each tile's bfloat16 one-hot(hi), [S, rows]."""
    t, s = rep[2].shape
    n_hi = -(-(num_links + 1) // paths_mod._MXU_LANES)
    return s * -(-n_hi // t) * 2


def test_mxu_kind_holds_to_the_byte_budget(mxu_on_cpu, monkeypatch):
    """One contraction over every slot while its one-hot fits the budget;
    past it, tiles whose one-hot each fits it."""
    fp = _perm_paths(7, "ugal")
    f, k, l = fp.edges.shape
    n_hi = -(-(fp.num_links + 1) // paths_mod._MXU_LANES)
    nbytes = f * k * l * n_hi * 2
    monkeypatch.setattr(paths_mod, "_MXU_LOADS_MAX_BYTES", nbytes - 1)
    rep = fp.device_arrays()[1]
    assert rep[0] == "mxu_tiles"
    assert (_tile_onehot_bytes(rep, fp.num_links)
            <= paths_mod._MXU_TILE_MAX_BYTES)
    monkeypatch.setattr(paths_mod, "_MXU_LOADS_MAX_BYTES", nbytes)
    assert _perm_paths(7, "ugal").device_arrays()[1][0] == "mxu"


def _tiled(q, mode, monkeypatch, tiles_at_least=3):
    """Paths whose loads take several tiles: no whole one-hot fits, and a
    tile's budget is a sixth of the whole one-hot's."""
    fp = _perm_paths(q, mode)
    n_hi = -(-(fp.num_links + 1) // paths_mod._MXU_LANES)
    monkeypatch.setattr(paths_mod, "_MXU_LOADS_MAX_BYTES", 0)
    monkeypatch.setattr(paths_mod, "_MXU_TILE_MAX_BYTES",
                        fp.edges.size * n_hi // 6)
    rep = fp.device_arrays()[1]
    assert rep[0] == "mxu_tiles" and rep[2].shape[0] >= tiles_at_least
    return fp


@pytest.mark.parametrize("case", [
    (7, "min"), (7, "ugal"), (7, "ugal_pf"),
    (13, "min"), (13, "ugal"), (13, "ugal_pf")])
def test_mxu_tiles_loads_match_float64(mxu_on_cpu, monkeypatch, case):
    fp = _tiled(*case, monkeypatch)
    rng = np.random.default_rng(3)
    split = (rng.random(fp.valid.shape) ** 6 * fp.valid).astype(np.float32)
    ref, count = _exact_loads(fp, split)
    rho = _loads(fp, "mxu_tiles", split)
    assert np.all(np.abs(rho - ref) <= (count + 2) * _U * ref)


def test_mxu_tiles_certified_saturation_brackets_like_the_gather(
        monkeypatch):
    fp_pad = _perm_paths(13, "ugal")
    assert fp_pad.device_arrays()[1][0] == "pad"
    monkeypatch.setattr(paths_mod, "_MXU_LOADS_PLATFORMS", ("cpu",))
    fp_tiles = _tiled(13, "ugal", monkeypatch)
    pad = saturation_throughput(fp_pad, tol=0.05, certify=True,
                                cert_iters=1024)
    tiles = saturation_throughput(fp_tiles, tol=0.05, certify=True,
                                  cert_iters=1024)
    assert (tiles.sat_lo, tiles.sat_hi) == (pad.sat_lo, pad.sat_hi)
    assert tiles.value == pad.value
    assert tiles.cert.iters == pad.cert.iters


@pytest.mark.parametrize("budget", [2 ** 16, 2 ** 13, 2 ** 11, 2 ** 9, 1])
def test_mxu_tile_count_follows_the_budget(monkeypatch, budget):
    """The fewest tiles whose fullest one-hot fits the budget (one hi row
    a tile where none does), every slot in the tile of its edge, in edge
    order, and every unused slot weightless."""
    fp = _perm_paths(13, "ugal")
    f, k, _ = fp.edges.shape
    e = fp.edges.reshape(-1)
    fk = np.repeat(np.arange(f * k), fp.edges.shape[2])[e >= 0]
    order = np.argsort(e[e >= 0], kind="stable")
    edge, fk = e[e >= 0][order], fk[order]
    lanes = paths_mod._MXU_LANES
    n_hi = -(-(fp.num_links + 1) // lanes)
    per_hi = np.bincount(edge // lanes, minlength=n_hi)

    def fits(t):
        rows = -(-n_hi // t)
        fullest = max(per_hi[i:i + rows].sum() for i in range(0, n_hi, rows))
        return fullest * rows * 2 <= budget

    monkeypatch.setattr(paths_mod, "_MXU_TILE_MAX_BYTES", budget)
    slot_fk, slot_ids = paths_mod._mxu_tiles(edge, fk, n_hi, f * k)
    t, s = slot_fk.shape
    rows = -(-n_hi // t)
    assert (fits(t) or rows == 1) and not any(map(fits, range(1, t)))
    used = slot_fk < f * k
    assert used.sum() == len(edge) and np.all(slot_ids[~used] == 0)
    got = (slot_ids + np.arange(t)[:, None] * rows * lanes)[used]
    assert np.array_equal(got, edge) and np.array_equal(slot_fk[used], fk)
    assert np.all(slot_ids[used] < rows * lanes)


def test_float64_loads_with_tiles_take_the_gather(mxu_on_cpu, monkeypatch):
    fp = _tiled(7, "ugal", monkeypatch)
    eidx, rep, valid, is_min, first_edge, demand, _ = fp.device_arrays()

    def loads_ops(dtype):
        names = _op_names(fluid._certified_saturation.lower(
            eidx, rep[1:], rep[0], valid, is_min, first_edge, demand,
            fp.num_links, "ugal", 0.05, 64, 2, dtype, 0))
        return [n.split("fluid.loads/", 1)[1] for n in names
                if "fluid.loads/" in n]

    assert any("dot_general" in n for n in loads_ops("float32"))
    with jax.enable_x64(True):
        f64 = loads_ops("float64")
    assert any("gather" in n for n in f64)
    assert not any("dot_general" in n for n in f64)


def test_bf16_parts_add_up_to_the_float32_exactly():
    """The three bfloat16 parts of a float32 add back to it bit for bit,
    and each part is a bfloat16 value, down to where the last part would
    be subnormal (below 2^-103; XLA flushes subnormals to zero)."""
    rng = np.random.default_rng(11)
    x = ((1 + rng.random(4096)) * 2.0 ** rng.integers(-100, 120, 4096)
         ).astype(np.float32)
    x[:4] = [0.0, 1.0, np.float32(1 + 2 ** -23), np.float32(3.4e38)]
    parts = np.asarray(jax.jit(fluid._bf16_parts)(x))
    assert parts.dtype == jnp.bfloat16 and parts.shape == (4096, 3)
    p = parts.astype(np.float64)
    assert np.array_equal(p.sum(axis=1), x.astype(np.float64))
    # the split does not round: the head is the float32 cut to 8 bits
    head = (x.view(np.uint32) & np.uint32(0xFFFF0000)).view(np.float32)
    assert np.array_equal(p[:, 0], head.astype(np.float64))
