"""The ``dcnv2`` kind of the benchmark (``bench/models/dcnv2.py``) against
the program at ``dcnv2-tiny`` on the CPU: the same weights from a seed,
the same stream, the same loss, gradients and row-wise Adagrad step; its
work counts; the readers of the kind's scopes; and the tiny cell
through the whole harness."""
import dataclasses
import json
import math
from types import SimpleNamespace as NS

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import program, reference, run, traffic, work
from bench.tests import tiny
from repro.configs import DLRM_CONFIGS
from repro.data.synthetic import WORKLOADS, CTRWorkload
from repro.models import dlrm
from repro.optim import get_optimizer

SEED = 2 ** 31 + 17
# the program's dcnv2-tiny, as a configuration file states it
TINY = {"name": "dcnv2-tiny", "source": "test", "kind": "dcnv2",
        "embedding_dim": 16, "bottom_dims": [32, 16], "top_dims": [64, 32, 1],
        "cross_layers": 2, "cross_rank": 8,
        "tables": {"sizes": [2000, 2000, 100, 100, 100, 100],
                   "zipf_a": [1.1, 1.1, 1.05, 1.05, 1.05, 1.05],
                   "bag_sizes": [3, 1, 2, 1, 6, 1], "n_dense": 13,
                   "n_groups": 32, "group_frac": 0.7}}


def _config(name):
    return json.loads((tiny.ROOT / "bench" / "configs" / f"{name}.json")
                      .read_text())


def _leaves(tree):
    return {jax.tree_util.keystr(k): v
            for k, v in jax.tree_util.tree_flatten_with_path(tree)[0]}


def test_tiny_config_is_the_programs():
    cfg, wl = DLRM_CONFIGS["dcnv2-tiny"], WORKLOADS["dcnv2-tiny"]
    t = TINY["tables"]
    assert (cfg.embedding_dim, cfg.bottom_widths, cfg.top_widths,
            cfg.cross_layers, cfg.cross_rank) == (
        16, (32, 16), (64, 32, 1), 2, 8)
    assert (list(wl.table_sizes), list(wl.zipf_a), list(wl.bag_sizes)) == (
        t["sizes"], t["zipf_a"], t["bag_sizes"])


@pytest.mark.parametrize("cfg,arch", [(TINY, "dcnv2-tiny"),
                                      (_config("dlrm-dcnv2"), "dlrm-dcnv2")])
def test_sampler_reproduces_the_programs_stream(cfg, arch):
    sampler = traffic.sampler(cfg)
    wl = WORKLOADS[arch]
    assert (sampler.sizes, sampler.zipf_a, sampler.bag_sizes) == (
        wl.table_sizes, wl.zipf_a, wl.bag_sizes)
    assert sampler.width == wl.width and sampler.vocab == wl.vocab
    n = 3 if arch == "dcnv2-tiny" else 1
    for got, want in zip(zip(range(n), sampler.batches(SEED, 16)),
                         zip(range(n), wl.stream(SEED, 16))):
        for a, b in zip(got[1], want[1]):
            assert a.dtype == b.dtype and np.array_equal(a, b)


def test_reference_init_is_the_programs():
    model = reference.Model(TINY)
    key = jax.random.key(SEED)
    want = jax.jit(dlrm.init_params, static_argnums=(1, 2))(
        key, DLRM_CONFIGS["dcnv2-tiny"], WORKLOADS["dcnv2-tiny"])
    got = reference._init(model, jnp.float32, key)
    assert jax.tree.structure(got) == jax.tree.structure(want)
    g, w = _leaves(got), _leaves(want)
    assert list(g) == list(w)
    for k in w:
        assert g[k].dtype == w[k].dtype and np.array_equal(g[k], w[k]), k


def _batch(n=16):
    wl = WORKLOADS["dcnv2-tiny"]
    rng = np.random.default_rng(SEED)
    return (jnp.asarray(wl.sample_batch(rng, n)),
            jnp.asarray(wl.dense_batch(rng, n)),
            jnp.asarray(wl.label_batch(rng, n)))


def test_loss_gradients_and_adagrad_step_match_the_reference():
    """float32 at ``highest`` on both sides.  The two differ only in the
    order of their sums (the reference pools by scatter-add, the program
    by slices), so each number agrees to a few float32 roundings: 1e-6
    relative on the loss, 1e-5 of a leaf's largest entry on its
    gradient, and 1e-6 absolute on the weights after a step of lr 0.01
    (Adagrad's first step divides each row's gradient by its own norm,
    so a row's move is at most the learning rate)."""
    cfg = DLRM_CONFIGS["dcnv2-tiny"]
    model = reference.Model(TINY)
    sparse, dense, labels = _batch()
    params = dlrm.init_params(jax.random.key(SEED), cfg,
                              WORKLOADS["dcnv2-tiny"])
    lr = 0.01
    with jax.default_matmul_precision("highest"):
        loss, grads = jax.value_and_grad(dlrm.bce_loss)(
            params, cfg, sparse, dense, labels)
        ref_loss, ref_grads = jax.value_and_grad(
            lambda p: reference.bce(reference.forward(model, p, sparse,
                                                      dense), labels))(params)
        opt = get_optimizer("rowwise_adagrad", lr)
        stepped, acc = opt.update(grads, opt.init(params), params)
        ref_stepped, ref_acc = reference.adagrad(
            params, ref_grads, reference.adagrad_init(params), lr)
    assert math.isfinite(float(loss))
    np.testing.assert_allclose(loss, ref_loss, rtol=1e-6)
    g, rg = _leaves(grads), _leaves(ref_grads)
    assert list(g) == list(rg)
    for k in rg:
        scale = float(jnp.max(jnp.abs(rg[k])))
        assert scale > 0, k                          # every leaf learns
        np.testing.assert_allclose(g[k], rg[k], rtol=0, atol=1e-5 * scale,
                                   err_msg=k)
    s, rs = _leaves(stepped), _leaves(ref_stepped)
    for k in rs:
        np.testing.assert_allclose(s[k], rs[k], rtol=0, atol=1e-6,
                                   err_msg=k)
    a, ra = _leaves(acc), _leaves(ref_acc)
    for k in ra:
        np.testing.assert_allclose(a[k], ra[k], rtol=1e-5, err_msg=k)


def test_kind_loads_and_counts_its_work():
    cfg = _config("dlrm-dcnv2")
    model = reference.Model(cfg)
    assert model.kind == "dcnv2" and model.module.__name__ == "bench_kind_dcnv2"
    assert dict(model.table_leaves) == {"embed": 128}
    assert (model.F, model.V) == (26, 13_808_380)
    d = 128 + 26 * 128                               # x0: 3,456
    assert d == 3_456
    cross = 3 * (d * 512 + 512 * d + d)
    top = d * 1024 + 1024 * 1024 + 1024 * 512 + 512 * 256 + 256 * 1
    bottom = 13 * 512 + 512 * 256 + 256 * 128
    biases = (1024 + 1024 + 512 + 256 + 1) + (512 + 256 + 128)
    assert (cross, top, bottom, biases) == (10_627_200, 5_243_136,
                                            170_496, 3_713)
    assert work.dense_params(cfg) == cross + top + bottom + biases
    macs = top + bottom
    per_sample = 2 * macs + 3 * (2 * 2 * d * 512 + 3 * d) + 214 * 128
    assert work.forward_flops(cfg, 128) == 128 * per_sample
    # the one table leaf, 128 wide, with its row-wise accumulator
    assert work.train_step(cfg, 128, 20_000.0) == {
        "flops": 3 * 128 * per_sample,
        "bytes": 2 * 4 * (20_000.0 * (128 + 1) + cross + top + bottom
                          + biases)}


def test_configuration_states_the_published_model():
    cfg = _config("dlrm-dcnv2")
    t = cfg["tables"]
    assert (cfg["embedding_dim"], cfg["bottom_dims"], cfg["top_dims"],
            cfg["cross_layers"], cfg["cross_rank"]) == (
        128, [512, 256, 128], [1024, 1024, 512, 256, 1], 3, 512)
    assert sum(t["published_sizes"]) == 204_184_588
    assert t["sizes"] == [-(-n // 16) if n > 1_000_000 else n
                          for n in t["published_sizes"]]
    assert sum(t["bag_sizes"]) == 214 and len(t["bag_sizes"]) == 26
    bench = json.loads((tiny.ROOT / "BENCHMARK.json").read_text())
    entry = next(c for c in bench["configs"] if c["name"] == "dlrm-dcnv2")
    assert entry["reduced"] == list(cfg["reduced"]) == ["tables"]


def test_program_without_bags_stops_the_run(monkeypatch):
    """Under the program of a tree that has no per-field bags the kind's
    sampler ends the process (a non-zero exit, not a wrong number)."""
    fields = {f.name: (f.type, f) for f in dataclasses.fields(CTRWorkload)
              if f.name != "bag_sizes"}
    Older = dataclasses.make_dataclass(
        "CTRWorkload", [(n, t, f) for n, (t, f) in fields.items()],
        frozen=True)
    monkeypatch.setattr(program, "CTRWorkload", Older)
    with pytest.raises(SystemExit, match="unknown model kind 'dcnv2'"):
        traffic.sampler(TINY)


READERS = {"bag_pool_ms.train": "dlrm.bag_pool",
           "cross_ms.train": "dlrm.cross"}


@pytest.mark.parametrize("name", READERS)
def test_reader_ms_per_train_step(name):
    reader = tiny.ROOT / "bench" / "metrics" / f"{name}.py"
    ctx = {"reduced": NS(module_calls={"jit_train_jit": 4}), "scope_s": {}}
    assert run.read_metric(reader, ctx) is None
    ctx["scope_s"] = {READERS[name]: 0.068, "dlrm.forward": 1.0}
    assert run.read_metric(reader, ctx) == pytest.approx(17.0)
    ctx["reduced"] = NS(module_calls={})
    assert run.read_metric(reader, ctx) is None


def test_tiny_cell_runs_through_the_harness(tmp_path):
    """The tiny configuration as a cell of a benchmark copy, judged by
    the chip cell's limits (float32 matmuls on the CPU read far below
    them)."""
    root = tiny.copy_bench(tmp_path)
    tiny.add_cell(root, "dcnv2-tiny.train", TINY, "tiny.train", tiny.TRAIN,
                  tiny.limits("dlrm-dcnv2.esd.1c"))
    cell = run.Cell(root, "dcnv2-tiny.train")
    res = run.run_cell(cell, SEED, 1.0, False, tiny.CPU)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert res["metrics"]["train_samples_per_s"]["value"] > 0
