"""Pallas kernels vs pure-jnp oracles (interpret mode), shape/dtype sweeps."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hypothesis import given, settings, strategies as st

from repro.core import cost_matrix_np, hungarian_dispatch
from repro.kernels import auction_solve_pallas, cost_matrix_pallas
from repro.kernels.auction import auction_bids
from repro.kernels.emb_lookup import (pooled_lookup, pooled_lookup_quant,
                                      pooled_lookup_staged, staged_gather)
from repro.kernels.exchange_pack import gather_rows_pallas
from repro.kernels.ref import (auction_bids_ref, gather_rows_ref,
                               pooled_lookup_quant_ref, pooled_lookup_ref,
                               pooled_lookup_staged_ref, staged_gather_ref)


class TestPooledLookup:
    @pytest.mark.parametrize("block_f", [None, 2, 4, 16])
    @pytest.mark.parametrize("B,F,V,E", [
        (4, 3, 50, 16), (8, 7, 100, 130), (2, 1, 10, 128),
        (16, 5, 1000, 512), (1, 9, 33, 7),
    ])
    def test_shapes(self, rng, B, F, V, E, block_f):
        table = rng.standard_normal((V, E)).astype(np.float32)
        ids = rng.integers(-1, V, (B, F)).astype(np.int32)
        w = rng.random((B, F)).astype(np.float32)
        got = pooled_lookup(jnp.asarray(table), jnp.asarray(ids),
                            jnp.asarray(w), block_f=block_f)
        want = pooled_lookup_ref(jnp.asarray(table), jnp.asarray(ids),
                                 jnp.asarray(w))
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)

    @pytest.mark.parametrize("dtype", [np.float32, jnp.bfloat16])
    def test_blocked_dtypes(self, rng, dtype):
        table = jnp.asarray(rng.standard_normal((64, 32)), dtype)
        ids = jnp.asarray(rng.integers(-1, 64, (4, 6)), jnp.int32)
        got = pooled_lookup(table, ids, block_f=4)
        want = pooled_lookup_ref(table, ids)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-2, atol=2e-2)

    @pytest.mark.parametrize("dtype", [np.float32, jnp.bfloat16])
    def test_dtypes(self, rng, dtype):
        table = jnp.asarray(rng.standard_normal((64, 32)), dtype)
        ids = jnp.asarray(rng.integers(0, 64, (4, 6)), jnp.int32)
        got = pooled_lookup(table, ids)
        want = pooled_lookup_ref(table, ids)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-2, atol=2e-2)

    def test_all_pad_row(self, rng):
        table = jnp.asarray(rng.standard_normal((10, 8)), jnp.float32)
        ids = jnp.asarray([[-1, -1], [2, 3]], jnp.int32)
        got = np.asarray(pooled_lookup(table, ids))
        assert np.allclose(got[0], 0.0)

    @settings(max_examples=15, deadline=None)
    @given(st.integers(1, 6), st.integers(1, 8), st.integers(2, 40),
           st.integers(1, 96))
    def test_property_sweep(self, B, F, V, E):
        rng = np.random.default_rng(B * 1000 + F * 100 + V * 10 + E)
        table = rng.standard_normal((V, E)).astype(np.float32)
        ids = rng.integers(-1, V, (B, F)).astype(np.int32)
        got = pooled_lookup(jnp.asarray(table), jnp.asarray(ids))
        want = pooled_lookup_ref(jnp.asarray(table), jnp.asarray(ids))
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=3e-5, atol=3e-5)


class TestAuctionKernel:
    @pytest.mark.parametrize("k,n", [(16, 4), (100, 8), (257, 16), (64, 1)])
    def test_bids_match_ref(self, rng, k, n):
        cost = (rng.random((k, n)) * 10).astype(np.float32)
        minp = rng.random(n).astype(np.float32)
        un = rng.random(k) > 0.3
        bj, bid = auction_bids(jnp.asarray(cost), jnp.asarray(minp),
                               jnp.asarray(un), jnp.asarray(0.01))
        rj, rbid = auction_bids_ref(jnp.asarray(cost), jnp.asarray(minp),
                                    jnp.asarray(un), 0.01)
        if n > 1:
            np.testing.assert_array_equal(np.asarray(bj), np.asarray(rj))
        np.testing.assert_allclose(np.asarray(bid), np.asarray(rbid),
                                   rtol=1e-5, atol=1e-5)

    def test_solve_optimal(self, rng):
        k, n, m = 12, 3, 4
        c = rng.integers(0, 30, (k, n)).astype(np.float32)
        a, _ = auction_solve_pallas(c, m, eps=1.0 / (k + 1))
        ch = c[np.arange(k), hungarian_dispatch(c.astype(float), m)].sum()
        assert c[np.arange(k), np.asarray(a)].sum() == pytest.approx(ch)


class TestCostMatrixKernel:
    def test_matches_numpy(self, rng):
        n, V, k, F = 4, 200, 16, 6
        latest = rng.random((n, V)) > 0.5
        dirty = (rng.random((n, V)) > 0.8) & latest
        t = np.array([1.0, 1.0, 10.0, 10.0])
        samples = rng.integers(0, V, (k, F))
        samples[rng.random((k, F)) < 0.1] = -1
        want = cost_matrix_np(samples, latest, dirty, t)
        got = cost_matrix_pallas(jnp.asarray(samples), jnp.asarray(latest),
                                 jnp.asarray(dirty), jnp.asarray(t))
        np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5, atol=1e-5)


class TestRefBitwise:
    """Every main-path kernel against its kernels/ref.py oracle, bitwise,
    in interpret mode.  Row counts off the 8-row tile exercise the
    partial-tail DMA path; weights are 0/1 masks (the cost-matrix and
    pooled-history uses), whose products are exact — with fractional
    weights the interpreted kernel's fused multiply-add rounds once
    where the reference rounds twice (TestPooledLookup covers those to
    tolerance)."""

    SHAPES = [(3, 5, 6, 16), (8, 26, 1003, 512), (13, 7, 64, 130)]

    @pytest.mark.parametrize("block_f", [None, 3])
    @pytest.mark.parametrize("B,F,V,E", SHAPES)
    def test_pooled_lookup(self, rng, B, F, V, E, block_f):
        table = jnp.asarray(rng.standard_normal((V, E)), jnp.float32)
        ids = jnp.asarray(rng.integers(-1, V, (B, F)), jnp.int32)
        w = jnp.asarray(rng.random((B, F)) < 0.7, jnp.float32)
        np.testing.assert_array_equal(
            np.asarray(pooled_lookup(table, ids, w, block_f=block_f)),
            np.asarray(pooled_lookup_ref(table, ids, w)))

    @pytest.mark.parametrize("B,F,V,E", SHAPES)
    def test_pooled_lookup_staged(self, rng, B, F, V, E):
        C = max(V // 3, 1)
        table = jnp.asarray(rng.standard_normal((V, E)), jnp.float32)
        plane = jnp.asarray(rng.standard_normal((C, E)), jnp.float32)
        ids = rng.integers(-1, V, (B, F))
        slots = np.where(rng.random((B, F)) < 0.5,
                         rng.integers(0, C, (B, F)), -1)
        slots[ids < 0] = -1
        ids, slots = jnp.asarray(ids, jnp.int32), jnp.asarray(slots, jnp.int32)
        np.testing.assert_array_equal(
            np.asarray(pooled_lookup_staged(plane, table, slots, ids)),
            np.asarray(pooled_lookup_staged_ref(plane, table, slots, ids)))

    @pytest.mark.parametrize("C,V,E", [(5, 6, 16), (21, 1003, 512),
                                       (64, 13, 130)])
    def test_staged_gather(self, rng, C, V, E):
        table = jnp.asarray(rng.standard_normal((V, E)), jnp.float32)
        plane = jnp.asarray(rng.standard_normal((C, E)), jnp.float32)
        src = jnp.asarray(np.where(rng.random(C) < 0.5,
                                   rng.integers(0, V, C), -1), jnp.int32)
        np.testing.assert_array_equal(
            np.asarray(staged_gather(plane, table, src)),
            np.asarray(staged_gather_ref(plane, table, src)))

    @pytest.mark.parametrize("dtype", [jnp.int32, jnp.float32])
    @pytest.mark.parametrize("m,F,S", [(5, 3, 7), (130, 74, 128),
                                       (16, 512, 19)])
    def test_gather_rows(self, rng, m, F, S, dtype):
        rows = jnp.asarray(rng.integers(0, 999, (m, F)), dtype)
        idx = jnp.asarray(np.where(rng.random(S) < 0.7,
                                   rng.integers(0, m, S), -1), jnp.int32)
        np.testing.assert_array_equal(
            np.asarray(gather_rows_pallas(rows, idx)),
            np.asarray(gather_rows_ref(rows, idx)))

    @pytest.mark.parametrize("codec", ["int8", "int8:32", "int4:7"])
    def test_pooled_lookup_quant(self, rng, codec):
        from repro.quant.codecs import quantize_rows

        V, E, B, F = 1003, 512, 11, 9
        codes, scale, zp = quantize_rows(
            jnp.asarray(rng.standard_normal((V, E)), jnp.float32), codec)
        ids = jnp.asarray(rng.integers(-1, V, (B, F)), jnp.int32)
        got = pooled_lookup_quant(codes, scale, zp, ids, codec=codec)
        # compiled like the interpreted kernel, so the dequantize
        # multiply-add contracts to one FMA on both sides
        want = jax.jit(pooled_lookup_quant_ref, static_argnums=4)(
            codes, scale, zp, ids, codec)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
