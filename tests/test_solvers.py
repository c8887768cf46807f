"""Assignment solvers: Hungarian oracle, SSP transportation, auction."""
import itertools

import numpy as np
import pytest

from hypothesis import given, settings, strategies as st

from repro.core import (
    assignment_cost,
    auction_dispatch,
    expand_capacity,
    hungarian,
    hungarian_dispatch,
)
from repro.core.ssp import ssp_dispatch


def brute_force(cost):
    n = cost.shape[0]
    return min(
        sum(cost[i, p[i]] for i in range(n))
        for p in itertools.permutations(range(n))
    )


class TestHungarian:
    def test_matches_bruteforce(self, rng):
        for _ in range(20):
            n = int(rng.integers(2, 7))
            c = rng.integers(0, 25, (n, n)).astype(float)
            assert assignment_cost(c, hungarian(c)) == pytest.approx(brute_force(c))

    def test_rectangular(self, rng):
        c = rng.random((3, 6))
        cols = hungarian(c)
        assert len(set(cols)) == 3  # distinct columns

    def test_rows_gt_cols_raises(self):
        with pytest.raises(ValueError):
            hungarian(np.zeros((3, 2)))

    def test_expand_capacity(self):
        c = np.arange(8, dtype=float).reshape(4, 2)
        e = expand_capacity(c, 2)
        assert e.shape == (4, 4)
        np.testing.assert_array_equal(e[:, 0], e[:, 1])

    def test_dispatch_capacity(self, rng):
        c = rng.random((12, 3))
        a = hungarian_dispatch(c, 4)
        assert np.bincount(a, minlength=3).max() <= 4


class TestSSP:
    @settings(max_examples=25, deadline=None)
    @given(st.integers(2, 5), st.integers(1, 4), st.data())
    def test_optimal_vs_hungarian(self, n, m, data):
        k = n * m
        c = np.array(
            data.draw(st.lists(st.lists(st.integers(0, 30), min_size=n,
                                        max_size=n), min_size=k, max_size=k)),
            dtype=float,
        )
        cs = c[np.arange(k), ssp_dispatch(c, m)].sum()
        ch = c[np.arange(k), hungarian_dispatch(c, m)].sum()
        assert cs == pytest.approx(ch)

    def test_partial_rows(self, rng):
        # k < n*m is allowed for SSP (unlike column expansion)
        c = rng.random((5, 4))
        a = ssp_dispatch(c, 2)
        assert np.bincount(a, minlength=4).max() <= 2

    def test_infeasible(self):
        with pytest.raises(ValueError):
            ssp_dispatch(np.zeros((9, 2)), 4)


class TestAuction:
    def test_exact_on_integers(self, rng):
        for _ in range(6):
            n = int(rng.integers(2, 5))
            m = int(rng.integers(1, 4))
            k = n * m
            c = rng.integers(0, 30, (k, n)).astype(float)
            ca = c[np.arange(k), auction_dispatch(c, m, exact=True)].sum()
            ch = c[np.arange(k), hungarian_dispatch(c, m)].sum()
            assert ca == pytest.approx(ch)

    def test_capacity_respected(self, rng):
        c = rng.random((32, 4))
        a = auction_dispatch(c, 8, exact=True)
        assert np.bincount(a, minlength=4).max() <= 8

    def test_constant_matrix(self):
        a = auction_dispatch(np.ones((8, 2)), 4)
        assert np.bincount(a, minlength=2).max() <= 4
