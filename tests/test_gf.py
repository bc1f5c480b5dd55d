"""Field axioms of GF(q) for primes and prime powers (hypothesis)."""
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.gf import GF, is_prime_power, primes_and_prime_powers

QS = [2, 3, 4, 5, 7, 8, 9, 11, 13, 16, 25, 27]


@pytest.mark.parametrize("q", QS)
def test_tables_are_field(q):
    gf = GF(q)
    a = np.arange(q)
    # additive group: 0 identity, inverses
    assert (gf.add(a, 0) == a).all()
    assert (gf.add(a, gf.neg(a)) == 0).all()
    # multiplicative: 1 identity, inverses for nonzero
    assert (gf.mul(a, 1) == a).all()
    nz = a[1:]
    assert (gf.mul(nz, gf.inv(nz)) == 1).all()
    # commutativity + no zero divisors
    assert (gf.mul_table == gf.mul_table.T).all()
    assert (gf.add_table == gf.add_table.T).all()
    prods = gf.mul_table[1:, 1:]
    assert (prods != 0).all()


@settings(max_examples=30, deadline=None)
@given(st.sampled_from([3, 5, 9, 13]), st.data())
def test_distributivity(q, data):
    gf = GF(q)
    x = data.draw(st.integers(0, q - 1))
    y = data.draw(st.integers(0, q - 1))
    z = data.draw(st.integers(0, q - 1))
    lhs = gf.mul(np.int32(x), gf.add(np.int32(y), np.int32(z)))
    rhs = gf.add(gf.mul(np.int32(x), np.int32(y)), gf.mul(np.int32(x), np.int32(z)))
    assert int(lhs) == int(rhs)


@settings(max_examples=25, deadline=None)
@given(st.sampled_from([5, 7, 9]), st.data())
def test_cross_product_orthogonal(q, data):
    gf = GF(q)
    u = np.array(data.draw(st.lists(st.integers(0, q - 1), min_size=3, max_size=3)))
    v = np.array(data.draw(st.lists(st.integers(0, q - 1), min_size=3, max_size=3)))
    c = gf.cross3(u, v)
    assert int(gf.dot3(u, c)) == 0
    assert int(gf.dot3(v, c)) == 0


def test_normalize3_leftmost_one():
    gf = GF(7)
    rng = np.random.default_rng(0)
    v = rng.integers(0, 7, size=(50, 3))
    n = gf.normalize3(v)
    for row in n[~(v == 0).all(axis=1)]:
        nz = row[row != 0]
        first = row[np.argmax(row != 0)]
        if (row != 0).any():
            assert first == 1


def test_prime_power_enumeration():
    assert primes_and_prime_powers(2, 32) == [2, 3, 4, 5, 7, 8, 9, 11, 13, 16,
                                              17, 19, 23, 25, 27, 29, 31, 32]
    assert not is_prime_power(12)
