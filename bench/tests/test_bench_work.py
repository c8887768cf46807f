"""Required-work counts: hand-worked small cases, and no count above
what a compiled implementation of the same work does."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import reference, work
from bench.tests.tiny import TINY

SMALL = {"kind": "wdl", "embedding_dim": 4, "mlp_dims": [8],
         "tables": {"sizes": [10, 6], "zipf_a": [1.1, 1.1], "n_dense": 2,
                    "n_groups": 1, "group_frac": 0.0, "hist_max": 3,
                    "hist_mean": 2.0}}


def test_small_wdl_by_hand():
    # bottom 2->8->4, top 4->8->1: 16+32 + 32+8 = 88 MACs, 176 FLOPs;
    # pooling (2 fields + 3 history slots) x 4 = 20 adds
    assert work.dense_params(SMALL) == 88
    assert work.forward_flops(SMALL, 1) == 176 + 20
    step = work.train_step(SMALL, rows=5, distinct=7)
    assert step["flops"] == 3 * 5 * 196
    # 7 rows of (4 embed + 1 wide) + 7 x 2 accumulators, 88 dense; r + w
    assert step["bytes"] == 2 * 4 * (7 * 5 + 7 * 2 + 88)


def test_small_dcn_by_hand():
    cfg = dict(SMALL, kind="dcn", cross_layers=1)
    d = 4 * (2 + 2)                      # 16-wide cross input
    assert work.dense_params(cfg) == (16 + 32) + (16 * 8 + 8) + 2 * d
    assert work.forward_flops(cfg, 2) == 2 * (
        2 * ((16 + 32) + (16 * 8 + 8)) + 5 * d + 3 * 4)


def test_kernels_by_hand():
    assert work.staged_gather(3, 512) == {"flops": 0.0, "bytes": 2 * 4 * 3 * 512}
    t, bound = work.least_seconds({"flops": 197e12, "bytes": 0.0},
                                  work.peaks("TPU v5 lite"))
    assert (t, bound) == (1.0, "flops")
    with pytest.raises(ValueError):
        work.peaks("TPU v9 imaginary")


def _xla(fn, *args):
    cost = jax.jit(fn).lower(*args).compile().cost_analysis()
    cost = cost[0] if isinstance(cost, list) else cost
    return cost["flops"], cost["bytes accessed"]


@pytest.mark.parametrize("kind", ["wdl", "dcn"])
def test_train_and_serve_counts_bound_xla(kind):
    cfg = dict(TINY, kind=kind, cross_layers=2)
    model = reference.Model(cfg)
    params = reference.init_params(model, 0)
    acc = reference.adagrad_init(params)
    rng = np.random.default_rng(0)
    sparse = jnp.asarray(rng.integers(-1, model.V, (32, model.F + 48)),
                         jnp.int32)
    dense = jnp.asarray(rng.standard_normal((32, 13)), jnp.float32)
    labels = jnp.asarray(rng.random(32) < 0.3, jnp.float32)
    distinct = work.distinct_ids(np.asarray(sparse))

    def train(p, a):
        loss, g = jax.value_and_grad(lambda q: reference.bce(
            reference.forward(model, q, sparse, dense), labels))(p)
        return reference.adagrad(p, g, a, 0.01)

    flops, nbytes = _xla(train, params, acc)
    need = work.train_step(cfg, 32, distinct)
    assert need["flops"] <= flops and need["bytes"] <= nbytes


def test_kernel_counts_bound_xla():
    table = jnp.ones((1000, 128))
    plane = jnp.zeros((64, 128))
    src = jnp.asarray(np.where(np.arange(64) % 3 == 0, np.arange(64) * 5,
                               -1), jnp.int32)

    def pull(plane, table, src):
        rows = table[jnp.clip(src, 0, 999)]
        return jnp.where((src >= 0)[:, None], rows, plane)

    _, nbytes = _xla(pull, plane, table, src)
    assert work.staged_gather(int((src >= 0).sum()), 128)["bytes"] <= nbytes
