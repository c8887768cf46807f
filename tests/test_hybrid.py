"""Heu, Theorem 1, and HybridDis (Alg. 2)."""
import numpy as np
import pytest

from hypothesis import given, settings, strategies as st

from repro.core import heu_dispatch, hungarian_dispatch, hybrid_dispatch, min2_minus_min


class TestHeu:
    def test_respects_capacity(self, rng):
        c = rng.random((20, 4))
        a = heu_dispatch(c, 5)
        assert np.bincount(a, minlength=4).max() <= 5

    def test_greedy_picks_min_when_free(self):
        c = np.array([[1.0, 2.0], [5.0, 0.5]])
        a = heu_dispatch(c, 2)
        assert a[0] == 0 and a[1] == 1

    def test_falls_through_on_full(self):
        c = np.array([[0.0, 1.0], [0.0, 1.0], [0.0, 1.0], [0.0, 1.0]])
        a = heu_dispatch(c, 2)
        assert np.bincount(a, minlength=2).tolist() == [2, 2]

    @settings(max_examples=30, deadline=None)
    @given(st.integers(2, 4), st.integers(1, 4), st.data())
    def test_theorem1_bound(self, n, m, data):
        """Per-row error of Heu <= min_{floor(i/m)+1} - min (row order)."""
        k = n * m
        c = np.array(
            data.draw(st.lists(st.lists(st.integers(0, 50), min_size=n,
                                        max_size=n), min_size=k, max_size=k)),
            dtype=float,
        )
        a = heu_dispatch(c, m)     # natural row order
        srt = np.sort(c, axis=1)
        for i in range(k):
            bound = srt[i, min(i // m + 1, n - 1)] - srt[i, 0]
            err = c[i, a[i]] - srt[i, 0]
            assert err <= bound + 1e-9, (i, err, bound)


class TestHybridDis:
    def test_alpha1_is_optimal(self, rng):
        c = rng.integers(0, 40, (12, 3)).astype(float)
        a = hybrid_dispatch(c, 4, alpha=1.0, opt="hungarian")
        opt = hungarian_dispatch(c, 4)
        assert c[np.arange(12), a].sum() == pytest.approx(
            c[np.arange(12), opt].sum())

    def test_alpha0_matches_sorted_heu(self, rng):
        c = rng.random((12, 3))
        a = hybrid_dispatch(c, 4, alpha=0.0)
        order = np.argsort(-min2_minus_min(c), kind="stable")
        b = heu_dispatch(c, 4, order=order)
        np.testing.assert_array_equal(a, b)

    @pytest.mark.parametrize("alpha", [0.0, 0.125, 0.25, 0.5, 0.75, 1.0])
    def test_feasible_all_alpha(self, rng, alpha):
        c = rng.random((24, 4))
        a = hybrid_dispatch(c, 6, alpha=alpha, opt="ssp")
        assert (a >= 0).all()
        assert np.bincount(a, minlength=4).max() <= 6

    def test_cost_monotone_in_alpha_on_average(self, rng):
        """Across many instances, mean cost decreases with alpha (Fig. 6)."""
        alphas = [0.0, 0.5, 1.0]
        totals = {a: 0.0 for a in alphas}
        for _ in range(15):
            c = rng.random((16, 4)) * rng.random(4)[None, :] * 10
            for a in alphas:
                d = hybrid_dispatch(c, 4, alpha=a, opt="ssp")
                totals[a] += c[np.arange(16), d].sum()
        assert totals[1.0] <= totals[0.5] + 1e-9
        assert totals[0.5] <= totals[0.0] + 1e-6

    def test_alpha_out_of_range(self):
        with pytest.raises(ValueError):
            hybrid_dispatch(np.zeros((4, 2)), 2, alpha=1.5)

    def test_infeasible_batch(self):
        with pytest.raises(ValueError):
            hybrid_dispatch(np.zeros((9, 2)), 4, alpha=0.5)
