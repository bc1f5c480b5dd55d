"""Kernels vs pure-jnp oracles (Pallas in interpret mode on CPU)."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.polarfly import build_polarfly
from repro.core.routing import all_pairs_distances
from repro.kernels.flash_attention.ops import attention
from repro.kernels.flash_attention.ref import attention_chunked, attention_ref
from repro.kernels.gf_crossprod.ops import intermediate_table
from repro.kernels.minplus.ops import apsp, minplus, path_costs
from repro.kernels.minplus.ref import minplus_ref, path_costs_ref


@pytest.mark.parametrize("shape", [(64, 64, 64), (130, 70, 50), (256, 33, 128)])
def test_minplus_matches_ref(shape):
    m, k, n = shape
    rng = np.random.default_rng(0)
    a = jnp.asarray(rng.random((m, k), dtype=np.float32) * 10)
    b = jnp.asarray(rng.random((k, n), dtype=np.float32) * 10)
    out = minplus(a, b, use_pallas=True, block=64)
    assert np.allclose(out, minplus_ref(a, b))


@pytest.mark.parametrize("q", [5, 7])
def test_apsp_kernel_matches_bfs(q):
    pf = build_polarfly(q)
    d_k = apsp(pf.graph.adjacency, use_pallas=True)
    d_ref = all_pairs_distances(pf.graph).astype(np.float32)
    assert np.allclose(d_k, d_ref)


@settings(max_examples=8, deadline=None)
@given(st.integers(2, 30), st.integers(2, 30))
def test_minplus_associativity_with_identity(m, n):
    """(A minplus I) == A with tropical identity (0 diag, inf off)."""
    rng = np.random.default_rng(m * 31 + n)
    a = jnp.asarray(rng.random((m, n), dtype=np.float32))
    eye = jnp.where(jnp.eye(n, dtype=bool), 0.0, 3.0e38 / 4).astype(jnp.float32)
    out = minplus(a, eye, use_pallas=True, block=32)
    assert np.allclose(out, a, atol=1e-6)


@pytest.mark.parametrize("shape", [(5, 3, 4), (300, 8, 5), (1, 1, 1)])
def test_path_costs_matches_ref(shape):
    """The fluid engines' per-candidate path-cost reduction: the XLA
    gather-and-sum agrees with the per-hop reference, including pad-slot
    gathers (index E reads the zero slot), both eagerly and under jit.
    Sums of L positive float32 terms in two orders differ by at most
    (L - 1) roundings of the total."""
    f, k, l = shape
    rng = np.random.default_rng(f * 7 + k * 3 + l)
    e = 37
    delay = jnp.asarray(np.concatenate(
        [rng.random(e).astype(np.float32) * 5, np.zeros(1, np.float32)]))
    eidx = jnp.asarray(rng.integers(0, e + 1, size=(f, k, l)), jnp.int32)
    ref = np.asarray(path_costs_ref(delay, eidx))
    exact = np.asarray(delay, np.float64)[np.asarray(eidx)].sum(axis=-1)
    rtol = max(l - 1, 1) * 2.0 ** -24
    for out in (path_costs(delay, eidx),
                jax.jit(path_costs)(delay, eidx)):
        np.testing.assert_allclose(np.asarray(out), ref, rtol=2 * rtol,
                                   atol=0)
        np.testing.assert_allclose(np.asarray(out), exact, rtol=rtol, atol=0)


@pytest.mark.parametrize("q", [3, 5, 7, 11])
def test_gf_crossprod_intermediates(q):
    pf = build_polarfly(q)
    core = pf.intermediates_all_pairs()
    off = ~np.eye(pf.n, dtype=bool)
    for use_pallas in (False, True):
        t = intermediate_table(pf.vertices, q, use_pallas=use_pallas)
        assert np.array_equal(t[off], core[off])


CASES = [
    # b, hq, hkv, s, d, causal, softcap, window
    (2, 4, 2, 128, 64, True, None, None),
    (1, 4, 4, 256, 64, True, 50.0, None),
    (1, 8, 2, 256, 128, True, None, 128),
    (1, 2, 1, 128, 64, False, None, None),
    (1, 2, 2, 128, 256, True, 30.0, 64),
]


@pytest.mark.parametrize("case", CASES)
@pytest.mark.parametrize("dtype", [np.float32, jnp.bfloat16])
def test_flash_attention_vs_ref(case, dtype):
    b, hq, hkv, s, d, causal, cap, win = case
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.standard_normal((b, hq, s, d)) * 0.5, dtype)
    k = jnp.asarray(rng.standard_normal((b, hkv, s, d)) * 0.5, dtype)
    v = jnp.asarray(rng.standard_normal((b, hkv, s, d)) * 0.5, dtype)
    out = attention(q, k, v, causal=causal, softcap=cap, window=win,
                    use_pallas=True, bq=64, bk=64)
    ref = attention_ref(q, k, v, causal=causal, softcap=cap, window=win)
    tol = 2e-6 if dtype == np.float32 else 2e-2
    assert np.allclose(np.asarray(out, np.float32),
                       np.asarray(ref, np.float32), atol=tol)


def test_chunked_attention_exact():
    rng = np.random.default_rng(1)
    q = jnp.asarray(rng.standard_normal((1, 4, 1024, 64)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((1, 2, 1024, 64)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((1, 2, 1024, 64)), jnp.float32)
    a = attention_ref(q, k, v, True, 50.0, 256)
    c = attention_chunked(q, k, v, True, 50.0, 256, block_q=128)
    assert np.allclose(a, c, atol=1e-5)
