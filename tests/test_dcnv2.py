"""DLRM-DCNv2 on the program's normal path: the ``dcnv2`` kind of
``models/dlrm.py`` (per-field sum-pooled bags over a static layout, a
low-rank cross, separate bottom and top widths, biases), its entries in
``configs/dlrm_configs.py`` and ``data/synthetic.py``, and ``run_dlrm``
training it through the ESD stages.  The configurations it shares code
with, ``wdl-s1`` and ``dcn-s3``, are pinned to what they were before it
came."""
import dataclasses
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import DLRM_CONFIGS
from repro.configs.dlrm_configs import DLRMConfig
from repro.data.synthetic import WORKLOADS, CTRWorkload
from repro.launch import train
from repro.models import dlrm

# Read off the tree before the dcnv2 kind came: each workload's first
# (sparse, dense, labels) batch of 4 from default_rng(7), hashed, and the
# forward's logits (float.hex of the float32) on 4 samples of
# default_rng(5) from weights of key 3, with every table cut to 50 rows.
PINNED = {
    "wdl-s1": {
        "config": DLRMConfig("wdl-s1", "wdl", "S1", embedding_dim=512,
                             n_dense=13, mlp_dims=(1024, 512, 256),
                             cross_layers=3, family="dlrm"),
        "table_sizes": (120_000,) * 4 + (1_000,) * 22,
        "zipf_a": (1.25,) * 4 + (1.1,) * 22,
        "stream": "ecb5196877f1ee4557671775310b434a0d3149a8f820554d852efb30d00c0b57",
        "logits": ["-0x1.2889ae0000000p-4", "-0x1.db5a940000000p-3",
                   "-0x1.e7b0740000000p-5", "0x1.778aee0000000p-4"]},
    "dcn-s3": {
        "config": DLRMConfig("dcn-s3", "dcn", "S3", embedding_dim=512,
                             n_dense=13, mlp_dims=(1024, 512, 256),
                             cross_layers=3, family="dlrm"),
        "table_sizes": (150_000,) * 3 + (2_000,) * 23,
        "zipf_a": (1.2,) * 3 + (1.15,) * 23,
        "stream": "2e48bf813f90b4e7fe0198900a8c55c81b60b0032ca9f82650ef0804d8ab8f4a",
        "logits": ["0x1.8797b00000000p-10", "-0x1.fa7ab60000000p-7",
                   "-0x1.92c3e00000000p-9", "-0x1.ba06180000000p-7"]},
}

# mlcommons/training recommendation_v2/torchrec_dlrm
PUBLISHED_ROWS = (40_000_000, 39_060, 17_295, 7_424, 20_265, 3, 7_122,
                  1_543, 63, 40_000_000, 3_067_956, 405_282, 10, 2_209,
                  11_938, 155, 4, 976, 14, 40_000_000, 40_000_000,
                  40_000_000, 590_152, 12_973, 108, 36)
MULTI_HOT = (3, 2, 1, 2, 6, 1, 1, 1, 1, 7, 3, 8, 1, 6, 9, 5, 1, 1, 1, 12,
             100, 27, 10, 3, 1, 1)


@pytest.mark.parametrize("name", PINNED)
def test_todays_configs_are_unchanged(name):
    pin = PINNED[name]
    cfg = DLRM_CONFIGS[name]
    assert cfg == pin["config"]
    assert (cfg.bottom_dims, cfg.top_dims, cfg.cross_rank) == ((), (), 0)
    assert cfg.bottom_widths == (1024, 512, 256, 512)
    assert cfg.top_widths == (1024, 512, 256, 1)
    wl = WORKLOADS[cfg.workload]
    assert wl == CTRWorkload(cfg.workload, cfg.kind, pin["table_sizes"],
                             pin["zipf_a"], n_dense=13, n_groups=32,
                             group_frac=0.7, hist_max=48, hist_mean=12.0)
    assert wl.bag_sizes == () and wl.bags == (1,) * 26
    assert wl.width == 74

    rng = np.random.default_rng(7)
    h = hashlib.sha256()
    for a in (wl.sample_batch(rng, 4), wl.dense_batch(rng, 4),
              wl.label_batch(rng, 4)):
        h.update(np.ascontiguousarray(a).tobytes())
    assert h.hexdigest() == pin["stream"]

    small = dataclasses.replace(wl, table_sizes=(50,) * wl.n_fields)
    params = dlrm.init_params(jax.random.key(3), cfg, small)
    rng = np.random.default_rng(5)
    sparse, dense = small.sample_batch(rng, 4), small.dense_batch(rng, 4)
    logits = jax.jit(lambda p, s, d: dlrm.forward(p, cfg, s, d))(
        params, jnp.asarray(sparse), jnp.asarray(dense))
    assert [float(x).hex() for x in np.asarray(logits)] == pin["logits"]


def test_bags_of_one_pool_to_a_per_field_gather():
    rng = np.random.default_rng(0)
    table = jnp.asarray(rng.standard_normal((300, 8)), jnp.float32)
    ids = jnp.asarray(rng.integers(0, 300, (5, 6)))
    rows = table[ids]
    assert np.array_equal(dlrm.bag_pool(rows, (1,) * 6), rows)
    # a workload of bags of one draws today's one id per field
    wl = WORKLOADS["dcnv2-tiny"]
    ones = dataclasses.replace(wl, bag_sizes=(1,) * wl.n_fields)
    plain = dataclasses.replace(wl, bag_sizes=())
    assert ones.width == plain.width == wl.n_fields
    assert np.array_equal(ones.sample_batch(np.random.default_rng(4), 9),
                          plain.sample_batch(np.random.default_rng(4), 9))


def test_bag_pool_sums_each_fields_columns():
    bags = WORKLOADS["dcnv2-tiny"].bag_sizes
    rng = np.random.default_rng(1)
    rows = rng.standard_normal((3, sum(bags), 4)).astype(np.float32)
    want, o = [], 0
    for n in bags:
        want.append(sum(rows[:, o + i] for i in range(n)))
        o += n
    np.testing.assert_allclose(dlrm.bag_pool(jnp.asarray(rows), bags),
                               np.stack(want, axis=1), rtol=1e-6)


def test_stream_lays_out_each_fields_bag():
    wl = WORKLOADS["dcnv2-tiny"]
    sparse = wl.sample_batch(np.random.default_rng(2), 64)
    assert sparse.shape == (64, wl.width) and wl.width == sum(wl.bag_sizes)
    assert (sparse >= 0).all()                       # no PAD
    off, o = wl.offsets(), 0
    for f, n in enumerate(wl.bag_sizes):
        cols = sparse[:, o:o + n]
        assert ((cols >= off[f]) & (cols < off[f] + wl.table_sizes[f])).all()
        o += n


def test_dlrm_dcnv2_is_one_chips_share_at_published_widths():
    cfg = DLRM_CONFIGS["dlrm-dcnv2"]
    assert (cfg.kind, cfg.embedding_dim, cfg.bottom_widths, cfg.top_widths,
            cfg.cross_layers, cfg.cross_rank) == (
        "dcnv2", 128, (512, 256, 128), (1024, 1024, 512, 256, 1), 3, 512)
    wl = WORKLOADS[cfg.workload]
    # the six tables over 1M rows row-wise over 16 chips, the rest whole
    assert wl.table_sizes == tuple(-(-n // 16) if n > 1_000_000 else n
                                   for n in PUBLISHED_ROWS)
    assert wl.vocab == 13_808_380 and sum(PUBLISHED_ROWS) == 204_184_588
    assert wl.bag_sizes == MULTI_HOT and wl.hist_max == 0
    assert wl.width == 214


def test_bad_shapes_are_refused():
    with pytest.raises(ValueError, match="bag sizes"):
        dataclasses.replace(WORKLOADS["dcnv2-tiny"], bag_sizes=(1, 2))
    with pytest.raises(ValueError, match="cross_rank"):
        dataclasses.replace(DLRM_CONFIGS["dcnv2-tiny"], cross_rank=0)


def test_dcnv2_params_are_the_published_layers():
    cfg, wl = DLRM_CONFIGS["dcnv2-tiny"], WORKLOADS["dcnv2-tiny"]
    p = dlrm.init_params(jax.random.key(0), cfg, wl)
    d = 16 + 16 * wl.n_fields                        # x0: bottom out + F bags
    shapes = jax.tree.map(lambda a: a.shape, p)
    assert shapes == {
        "embed": (wl.vocab, 16),
        "bottom": [{"w": (13, 32), "b": (32,)}, {"w": (32, 16), "b": (16,)}],
        "top": [{"w": (d, 64), "b": (64,)}, {"w": (64, 32), "b": (32,)},
                {"w": (32, 1), "b": (1,)}],
        "cross": [{"v": (d, 8), "w": (8, d), "b": (d,)}] * 2}


def test_serving_refuses_per_field_bags():
    from repro.launch import serve
    args = serve.build_parser().parse_args(["--arch", "dcnv2-tiny"])
    with pytest.raises(SystemExit, match="per-field bags"):
        serve.run_serve(args)


def test_run_dlrm_trains_dcnv2_through_the_esd_stages():
    """The entry point and knobs the benchmark's esd.1c traffic gives."""
    args = train.build_parser().parse_args(
        ["--arch", "dcnv2-tiny", "--steps", "6", "--batch-per-worker", "8",
         "--esd-alpha", "1.0", "--exchange", "ragged",
         "--pipeline-depth", "2", "--lookahead", "4", "--prefetch", "16",
         "--prefetch-slots", "64", "--capacity-ratio", "0.2"])
    args.verbose = False
    recs = train.run_dlrm(args)
    assert [r["step"] for r in recs] == list(range(6))
    assert all(np.isfinite(r["loss"]) for r in recs)
    assert all(r["exchange_overflow"] == 0 for r in recs)
    # the cache sees every id of a bag: a first step misses them all
    first = WORKLOADS["dcnv2-tiny"].sample_batch(
        np.random.default_rng(args.seed + 1), 8)
    assert recs[0]["miss_pull"] == np.unique(first).size
    assert sum(r["prefetch_bytes"] for r in recs) > 0
