"""Ahead-of-time compiles of the main-path Pallas kernels for TPU v5e.

The only test file that describes the chip.  Each test compiles one
kernel at the paper's embedding width (E = 512) against the S1 table
(502k rows, ~1.03 GB f32) for one chip of a described ``v5e:2x2``
topology, with ``interpret=False``: Mosaic's tiling, memory-space and
VMEM checks run here, with no chip attached.  The last tests compile
whole train steps at their registered sizes: the scopes of the DCNv2
cross, and the temporaries of the update of the step's rows.  Nothing
runs, so these tests say nothing about results or times —
tests/test_kernels.py and friends check results in interpret mode,
``chip_smoke.py`` on the chip.

The topology is described inside a module-scoped fixture, never at
import time: only one process may load the TPU library at a time, and
under pytest-xdist every worker imports this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest

from repro.configs import DLRM_CONFIGS
from repro.data.synthetic import WORKLOADS
from repro.launch.steps import make_dlrm_train_jit
from repro.models import dlrm
from repro.optim import get_optimizer
from test_named_scopes import assert_cross_scoped, cross_products
from repro.kernels.emb_lookup import (pooled_lookup, pooled_lookup_quant,
                                      pooled_lookup_staged, staged_gather)
from repro.kernels.exchange_pack import (gather_rows_pallas,
                                         gather_rows_quant_pallas)

E = 512
V = WORKLOADS["S1"].vocab          # 502,000 rows
F = WORKLOADS["S1"].n_fields       # 26 single-hot fields
HIST = WORKLOADS["S1"].hist_max    # 48 multi-hot history slots
CODEC = "int8:32"
G = E // 32


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one; keep the cache out of it
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield jax.sharding.SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


# name -> (kernel, [(shape, dtype), ...] positional args, static kwargs,
#          temporary bytes allowed: no operand is ever copied whole)
CASES = {
    "pooled_lookup": (
        pooled_lookup,
        [((V, E), jnp.float32), ((256, F), jnp.int32),
         ((256, F), jnp.float32)], {}, 1 << 20),
    "pooled_lookup_block_f": (
        pooled_lookup,
        [((V, E), jnp.float32), ((256, F), jnp.int32),
         ((256, F), jnp.float32)], {"block_f": 8}, 1 << 20),
    "staged_gather": (
        staged_gather,
        [((512, E), jnp.float32), ((V, E), jnp.float32), ((512,), jnp.int32)],
        {}, 1 << 20),
    "pooled_lookup_staged": (
        pooled_lookup_staged,
        [((V // 4, E), jnp.float32), ((V, E), jnp.float32),
         ((16, HIST), jnp.int32), ((16, HIST), jnp.int32)], {}, 1 << 20),
    "pooled_lookup_quant": (
        pooled_lookup_quant,
        [((V, E), jnp.float32), ((V, G), jnp.float32), ((V, G), jnp.float32),
         ((256, F), jnp.int32)], {"codec": CODEC}, 8 << 20),
    "gather_rows_pallas": (
        gather_rows_pallas,
        [((128, E), jnp.float32), ((128,), jnp.int32)], {}, 1 << 20),
    "gather_rows_pallas_ids": (
        gather_rows_pallas,
        [((128, F + HIST), jnp.int32), ((128,), jnp.int32)], {}, 1 << 20),
    "gather_rows_quant_pallas": (
        gather_rows_quant_pallas,
        [((128, E), jnp.float32), ((128,), jnp.int32)], {"codec": CODEC},
        1 << 20),
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_compiles_for_v5e(one_chip, name):
    fn, args, kw, temp_budget = CASES[name]
    sds = [_sds(s, d, one_chip) for s, d in args]
    compiled = fn.lower(*sds, interpret=False, **kw).compile()
    assert "tpu_custom_call" in compiled.as_text()
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp <= temp_budget, (name, temp)


def test_dcnv2_cross_fusions_carry_their_scope(one_chip):
    """At the published widths, as XLA fuses the train step for a v5e:
    every fusion that holds a product of the low-rank cross, forward and
    backward, is filed under ``dlrm.cross``, the scope that
    ``cross_ms.train`` reads from the device trace (the weight-gradient
    products fuse with the optimizer's squares, and keep the name)."""
    cfg = DLRM_CONFIGS["dlrm-dcnv2"]
    wl = WORKLOADS[cfg.workload]
    optimizer = get_optimizer("rowwise_adagrad", 1e-2)
    params = jax.eval_shape(
        lambda: dlrm.init_params(jax.random.key(0), cfg, wl))
    state = jax.eval_shape(optimizer.init, params)
    train_jit = make_dlrm_train_jit(cfg, optimizer, dlrm.bce_loss_masked)
    B = 128
    args = jax.tree.map(lambda a: _sds(a.shape, a.dtype, one_chip),
                        (params, state))
    text = train_jit.lower(
        *args, _sds((B, wl.width), jnp.int32, one_chip),
        _sds((B, wl.n_dense), jnp.float32, one_chip),
        _sds((B,), jnp.float32, one_chip)).compile().as_text()
    d = cfg.bottom_widths[-1] + cfg.embedding_dim * wl.n_fields
    assert_cross_scoped(cross_products(text, d, cfg.cross_rank),
                        cfg.cross_layers)


@pytest.mark.parametrize("arch", ["wdl-s1", "dlrm-dcnv2"])
def test_train_step_temporaries_stay_off_the_table(one_chip, arch):
    """At the program's registered sizes and the published widths, 128
    samples a step: the train step's temporaries are under a tenth of the
    embedding table's bytes, so the tables are updated on the step's
    rows and no gradient of a whole table is built."""
    cfg = DLRM_CONFIGS[arch]
    wl = WORKLOADS[cfg.workload]
    optimizer = get_optimizer("rowwise_adagrad", 1e-2)
    params = jax.eval_shape(
        lambda: dlrm.init_params(jax.random.key(0), cfg, wl))
    state = jax.eval_shape(optimizer.init, params)
    train_jit = make_dlrm_train_jit(cfg, optimizer, dlrm.bce_loss)
    B = 128
    args = jax.tree.map(lambda a: _sds(a.shape, a.dtype, one_chip),
                        (params, state))
    compiled = train_jit.lower(
        *args, _sds((B, wl.width), jnp.int32, one_chip),
        _sds((B, wl.n_dense), jnp.float32, one_chip),
        _sds((B,), jnp.float32, one_chip)).compile()
    table = wl.vocab * cfg.embedding_dim * 4
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < table / 10, (arch, temp, table)
