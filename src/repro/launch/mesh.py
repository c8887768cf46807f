"""Every device mesh the program builds.

Defined as FUNCTIONS so importing this module never touches jax device
state (the dry-run sets XLA_FLAGS for 512 host devices *before* any jax
init; tests and benches see the real single CPU device).

Each mesh names its axis types: ``Auto`` on every axis, so shardings
propagate through jit as before.  JAX's default for ``jax.make_mesh`` is
``Explicit`` axes (sharding in types), under which the DLRM table gather
refuses to trace without an explicit ``out_sharding``.

TPU v5e constants used by the roofline (benchmarks/roofline.py):
  197 TFLOP/s bf16 per chip, 819 GB/s HBM, ~50 GB/s/link ICI; the
  inter-pod DCN tier is modeled at ~1/8 ICI — the 2-tier heterogeneous
  network that ESD's bandwidth-weighted cost matrix exploits (DESIGN.md §2).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType

PEAK_FLOPS = 197e12          # bf16 per chip
HBM_BW = 819e9               # bytes/s per chip
ICI_BW = 50e9                # bytes/s per link (intra-pod)
DCN_BW = 6.25e9              # bytes/s per link (inter-pod tier)


def make_mesh(shape, axes, devices=None):
    """A mesh of ``shape`` over ``axes`` with ``Auto`` axis types."""
    return jax.make_mesh(tuple(shape), tuple(axes), devices=devices,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(model_parallel: int = 1):
    """(data, model) mesh over whatever devices exist: the launchers'
    single-host mesh."""
    n = len(jax.devices())
    return make_mesh((n // model_parallel, model_parallel), ("data", "model"))
