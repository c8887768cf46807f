"""repro.quant: codecs, fused kernels, wire paths, and cost pricing.

Contracts under test:
  * per-group affine round-trip error |x - deq(q(x))| <= scale / 2
    (property-tested over widths, blocks, and value ranges), with
    constant rows — PAD planes in particular — round-tripping EXACTLY;
  * int4 nibble pack/unpack is lossless for every embedding width
    parity (odd widths carry a zero high nibble in the last byte);
  * fake_quant == dequantize(quantize) and ste passes gradients through
    the quantizer unchanged;
  * quantize_with_feedback conserves mass: g_hat + residual' ==
    g + residual (error feedback never loses gradient);
  * the fused Pallas pack+quantize kernel matches quantize_rows on the
    gathered block (zp exact, scale to 1 ULP, codes within one step);
  * pooled_lookup_quant(q(table)) == pooled_lookup(fake_quant(table));
  * byte helpers: int8 payload is exactly E bytes (4x fp32), meta is a
    separate side channel; transmission_time_codec(None) is bitwise
    transmission_time, and per-link codecs re-price each link;
  * the train driver runs with --codec and the simulator reports the
    quant byte census.
"""
import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, "tests")
from hypothesis import given, settings, strategies as st

from repro.core.cost import transmission_time, transmission_time_codec
from repro.quant import (
    CODEC_NAMES,
    Codec,
    codec_name,
    dequantize_rows,
    fake_quant,
    get_codec,
    meta_row_bytes,
    pack_int4,
    quantize_rows,
    quantize_with_feedback,
    resolve_link_codecs,
    row_wire_bytes,
    ste,
    unpack_int4,
    wire_row_bytes,
)


@pytest.fixture
def rng():
    return np.random.default_rng(0)


INT_CODECS = ["int8", "int4", "int8:8", "int4:7"]


class TestCodecSpec:
    def test_get_codec(self):
        assert get_codec(None) is None
        assert get_codec("none") is None
        assert get_codec("fp32") is None
        c = get_codec("int4:32")
        assert isinstance(c, Codec)
        assert c.kind == "int4" and c.block == 32 and c.bits == 4
        assert c.levels == 15 and c.name == "int4:32"
        assert {"fp16", "int8", "int4"} <= set(CODEC_NAMES)
        assert codec_name(None) == "fp32"
        assert codec_name("int8") == "int8"
        assert get_codec(c) is c
        with pytest.raises(ValueError):
            get_codec("int3")

    def test_wire_bytes(self):
        E = 32
        assert wire_row_bytes(E, None) == 4 * E
        assert wire_row_bytes(E, "fp16") == 2 * E
        assert wire_row_bytes(E, "int8") == E          # exactly 4x
        assert wire_row_bytes(E, "int4") == E // 2     # exactly 8x
        assert wire_row_bytes(7, "int4") == 4          # odd width rounds up
        assert meta_row_bytes(E, None) == 0
        assert meta_row_bytes(E, "fp16") == 0
        assert meta_row_bytes(E, "int8") == 8          # scale + zp, 1 group
        assert meta_row_bytes(E, "int8:8") == 8 * 4    # one pair per group
        # meta is charged on top of the payload, never inside it
        assert row_wire_bytes(E, "int8") == wire_row_bytes(E, "int8") + 8


class TestRoundTrip:
    @settings(max_examples=40, deadline=None)
    @given(st.sampled_from(INT_CODECS), st.integers(1, 9),
           st.integers(1, 12), st.floats(0.1, 100.0),
           st.integers(0, 2 ** 31 - 1))
    def test_error_bound(self, codec, rows, width, span, seed):
        r = np.random.default_rng(seed)
        x = jnp.asarray(r.uniform(-span, span, (rows, width)), jnp.float32)
        codes, scale, zp = quantize_rows(x, codec)
        y = dequantize_rows(codes, scale, zp, codec)
        c = get_codec(codec)
        B = width if c.block is None else min(c.block, width)
        G = -(-width // B)
        # expand per-group scale to columns for the bound
        col_scale = np.repeat(np.asarray(scale), B, axis=1)[:, :width]
        err = np.abs(np.asarray(x) - np.asarray(y))
        assert (err <= col_scale / 2 + 1e-6).all()
        assert scale.shape == (rows, G) and zp.shape == (rows, G)

    @pytest.mark.parametrize("codec", INT_CODECS)
    @pytest.mark.parametrize("width", [1, 2, 3, 7, 8])
    def test_edge_widths(self, codec, width, rng):
        x = jnp.asarray(rng.normal(size=(5, width)), jnp.float32)
        y = dequantize_rows(*quantize_rows(x, codec), codec)
        assert y.shape == x.shape
        assert np.isfinite(np.asarray(y)).all()

    @pytest.mark.parametrize("codec", INT_CODECS + ["fp16"])
    def test_constant_rows_exact(self, codec):
        """PAD planes (-1 everywhere) and any constant row round-trip
        exactly: zero range pins scale to 1 and zp to the value."""
        for v in (-1.0, 0.0, 3.5):
            x = jnp.full((3, 8), v, jnp.float32)
            y = dequantize_rows(*quantize_rows(x, codec), codec)
            np.testing.assert_array_equal(np.asarray(y), np.asarray(x))

    def test_fp16_is_cast(self, rng):
        x = jnp.asarray(rng.normal(size=(4, 6)), jnp.float32)
        codes, scale, zp = quantize_rows(x, "fp16")
        assert codes.dtype == jnp.float16
        np.testing.assert_array_equal(
            np.asarray(dequantize_rows(codes, scale, zp, "fp16")),
            np.asarray(x.astype(jnp.float16).astype(jnp.float32)))

    def test_int4_nibble_pack(self, rng):
        for width in (1, 2, 3, 7, 8):
            codes = jnp.asarray(rng.integers(0, 16, (6, width)), jnp.int32)
            packed = pack_int4(codes)
            assert packed.shape == (6, (width + 1) // 2)
            assert packed.dtype == jnp.uint8
            np.testing.assert_array_equal(
                np.asarray(unpack_int4(packed, width)), np.asarray(codes))


class TestGradients:
    def test_fake_quant_matches_round_trip(self, rng):
        x = jnp.asarray(rng.normal(size=(4, 8)), jnp.float32)
        for codec in INT_CODECS + ["fp16"]:
            want = dequantize_rows(*quantize_rows(x, codec), codec)
            np.testing.assert_array_equal(np.asarray(fake_quant(x, codec)),
                                          np.asarray(want))

    def test_ste_gradient_passthrough(self, rng):
        x = jnp.asarray(rng.normal(size=(3, 8)), jnp.float32)
        # forward: quantized value; backward: identity (straight-through)
        np.testing.assert_array_equal(np.asarray(ste(x, "int8")),
                                      np.asarray(fake_quant(x, "int8")))
        g = jax.grad(lambda v: (ste(v, "int8") ** 2).sum())(x)
        # d/dx of q(x)^2 with dq/dx := 1 is 2 * q(x)
        np.testing.assert_allclose(np.asarray(g),
                                   2 * np.asarray(fake_quant(x, "int8")),
                                   rtol=1e-6)

    def test_feedback_conserves_gradient(self, rng):
        g = jnp.asarray(rng.normal(size=(16, 8)), jnp.float32)
        res = jnp.asarray(rng.normal(size=(16, 8)) * 0.01, jnp.float32)
        g_hat, res2 = quantize_with_feedback(g, res, "int4")
        np.testing.assert_allclose(np.asarray(g_hat + res2),
                                   np.asarray(g + res), rtol=1e-5,
                                   atol=1e-6)
        # the residual shrinks the NEXT step's error: quantizing the
        # accumulator, not the raw grad, is what makes int4 trainable
        assert np.abs(np.asarray(res2)).max() <= \
            np.abs(np.asarray(quantize_rows(g + res, "int4")[1])).max() + 1e-6


class TestFusedKernels:
    @pytest.mark.parametrize("codec", INT_CODECS)
    def test_gather_quant_matches_reference(self, codec, rng):
        from repro.kernels.exchange_pack import gather_rows_quant_pallas

        rows = jnp.asarray(rng.normal(size=(10, 6)) * 3, jnp.float32)
        idx = jnp.asarray([3, -1, 0, 9, -1, 7], jnp.int32)
        codes, scale, zp = gather_rows_quant_pallas(rows, idx, codec=codec,
                                                    fill=-1)
        gathered = jnp.where((idx >= 0)[:, None], rows[jnp.maximum(idx, 0)],
                             -1.0)
        rcodes, rscale, rzp = quantize_rows(gathered, codec)
        # zp (group min) is exact; scale may differ by 1 ULP of backend
        # rounding in (hi - lo) / levels, flipping a boundary code by one
        np.testing.assert_array_equal(np.asarray(zp), np.asarray(rzp))
        np.testing.assert_allclose(np.asarray(scale), np.asarray(rscale),
                                   rtol=1e-6)
        assert np.abs(np.asarray(codes) -
                      np.asarray(rcodes, np.float32)).max() <= 1
        deq_k = dequantize_rows(codes, scale, zp, codec)
        deq_r = dequantize_rows(rcodes, rscale, rzp, codec)
        np.testing.assert_allclose(np.asarray(deq_k), np.asarray(deq_r),
                                   rtol=1e-5, atol=1e-5)
        # PAD slots dequantize exactly back to fill
        np.testing.assert_array_equal(
            np.asarray(deq_k)[np.asarray(idx) < 0], -1.0)

    def test_gather_quant_fp16(self, rng):
        from repro.kernels.exchange_pack import gather_rows_quant_pallas

        rows = jnp.asarray(rng.normal(size=(5, 4)), jnp.float32)
        idx = jnp.asarray([2, -1, 4], jnp.int32)
        codes, _, _ = gather_rows_quant_pallas(rows, idx, codec="fp16")
        assert codes.dtype == jnp.float16
        want = np.where((np.asarray(idx) >= 0)[:, None],
                        np.asarray(rows)[np.maximum(np.asarray(idx), 0)],
                        -1.0).astype(np.float16)
        np.testing.assert_array_equal(np.asarray(codes), want)

    @pytest.mark.parametrize("codec", ["int8", "int4:4", "fp16"])
    def test_pooled_lookup_quant(self, codec, rng):
        from repro.kernels.emb_lookup import pooled_lookup, pooled_lookup_quant

        V, E, B, F = 40, 8, 6, 5
        table = jnp.asarray(rng.normal(size=(V, E)), jnp.float32)
        ids = jnp.asarray(rng.integers(-1, V, (B, F)), jnp.int32)
        codes, scale, zp = quantize_rows(table, codec)
        got = pooled_lookup_quant(codes, scale, zp, ids, codec=codec)
        want = pooled_lookup(fake_quant(table, codec), ids)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)


class TestCostPricing:
    def test_none_is_bitwise_transmission_time(self, rng):
        bw = jnp.asarray(rng.uniform(1e6, 1e9, (8,)), jnp.float32)
        got = transmission_time_codec(16, bw, None)
        want = transmission_time(16 * 4.0, bw)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    def test_per_link_pricing(self):
        bw = np.array([1e6, 1e6], np.float64)
        links = np.array(["fp16", "int4"], object)
        t = np.asarray(transmission_time_codec(32, bw, links))
        # fp16: 64 B payload; int4: 16 B payload + 8 B scale/zp meta
        np.testing.assert_allclose(t, [64 / 1e6, 24 / 1e6])
        assert (t < np.asarray(transmission_time(32 * 4.0, bw))).all()

    def test_resolve_link_codecs(self):
        bw = np.array([1.0, 10.0, 100.0, 5.0])
        links = resolve_link_codecs("bandwidth", bw, "int4")
        # >= median (7.5) -> fp16 fast links, int4 slow links
        assert [codec_name(c) for c in links] == \
            ["int4", "fp16", "fp16", "int4"]
        uni = resolve_link_codecs("uniform", bw, "int8")
        assert all(codec_name(c) == "int8" for c in uni)
        assert resolve_link_codecs("uniform", bw, None) is None

    def test_simulator_quant_census(self):
        from repro.core import SimConfig, simulate
        from repro.data.synthetic import WORKLOADS

        wl = WORKLOADS["tiny"]
        kw = dict(workload=wl, n_workers=4, batch_per_worker=16,
                  embedding_dim=32, iters=6, warmup=2, seed=0)
        base = simulate(SimConfig(**kw))
        q = simulate(SimConfig(codec="int8", **kw))
        assert base.quant is None
        assert q.quant["codec"] == "int8"
        assert q.quant["byte_reduction"] == pytest.approx(4.0)
        assert q.quant["emb_wire_bytes"] * 4 == q.quant["emb_fp32_bytes"]
        assert q.quant["emb_meta_bytes"] > 0
        # bandwidth policy: fast links fp16, slow links the codec
        bw = np.array([1e9, 1e9, 1e6, 1e6])
        h = simulate(SimConfig(codec="int4", codec_policy="bandwidth",
                               bandwidths=bw, **kw))
        assert h.quant["link_codecs"] == {"fp16": 2, "int4": 2}


class TestDriver:
    def _run(self, argv, timeout=900):
        import os
        import subprocess

        root = Path(__file__).resolve().parents[1]
        # XLA_FLAGS stays behind: importing repro.launch.dryrun in this
        # worker rewrites it to 512 host devices
        env = {"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
               "HOME": os.environ.get("HOME", str(root)),
               "JAX_PLATFORMS": os.environ.get("JAX_PLATFORMS", "cpu")}
        for var in ("JAX_COMPILATION_CACHE_DIR",
                    "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"):
            if var in os.environ:
                env[var] = os.environ[var]
        return subprocess.run(
            [sys.executable, "-m"] + argv, capture_output=True, text=True,
            timeout=timeout, cwd=root, env=env)

    def test_codec_none_matches_default(self):
        """--codec none is the bitwise default path (the quant branch is
        structurally never taken)."""
        base = self._run(["repro.launch.train", "--arch", "wdl-tiny",
                          "--steps", "4", "--smoke"])
        none = self._run(["repro.launch.train", "--arch", "wdl-tiny",
                          "--steps", "4", "--smoke", "--codec", "none"])
        assert base.returncode == 0, base.stderr[-2000:]
        assert none.returncode == 0, none.stderr[-2000:]
        # step records go to stderr (obs.log_step); scan both streams
        get = lambda r: [json.loads(l)["loss"]
                         for l in (r.stdout + r.stderr).splitlines()
                         if l.startswith("{")]
        assert get(base) == get(none)

    def test_int8_trains(self):
        res = self._run(["repro.launch.train", "--arch", "wdl-tiny",
                         "--steps", "6", "--smoke", "--codec", "int8"])
        assert res.returncode == 0, res.stderr[-2000:]
        recs = [json.loads(l)
                for l in (res.stdout + res.stderr).splitlines()
                if l.startswith("{")]
        losses = [r["loss"] for r in recs]
        assert losses and all(np.isfinite(losses))
        assert losses[-1] < losses[0]        # still learning under int8

    def test_codec_needs_ragged_with_esd(self):
        res = self._run(["repro.launch.train", "--arch", "wdl-tiny",
                         "--steps", "2", "--smoke", "--esd-alpha", "1",
                         "--codec", "int8"])
        assert res.returncode != 0
        assert "ragged" in (res.stderr + res.stdout)
