"""Ahead-of-time compiles of the main-path Pallas kernels for TPU v5e.

The only test file that describes the chip.  Each test compiles one
kernel at the paper's embedding width (E = 512) against the S1 table
(502k rows, ~1.03 GB f32) for one chip of a described ``v5e:2x2``
topology, with ``interpret=False``: Mosaic's tiling, memory-space and
VMEM checks run here, with no chip attached.  Nothing runs, so these
tests say nothing about results or times — tests/test_kernels.py and
friends check results in interpret mode, ``chip_smoke.py`` on the chip.

The topology is described inside a module-scoped fixture, never at
import time: only one process may load the TPU library at a time, and
under pytest-xdist every worker imports this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest

from repro.data.synthetic import WORKLOADS
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
