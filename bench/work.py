"""Required work of the benchmarked programs, from shapes and ids, and
the peaks of the chips they run on.  Each model kind counts its own
work outside the tables (``bench/models/<kind>.py``).

"Required" is the least any implementation of the same computation has
to do: each distinct embedding row read and written once, each dense
parameter read and written once, and the model's multiply-adds.  A share built on these counts can therefore not pass
100% for any implementation.
"""
from __future__ import annotations

import numpy as np

from . import models

# Published peaks per chip, keyed by jax's device_kind.  Source: Google
# Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 819 GB/s HBM.
PEAKS = {
    "TPU v5 lite": {"flops": 197e12, "hbm_bytes_per_s": 819e9},
}

F32 = 4


def peaks(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no peaks for device kind {device_kind!r}; add "
                         f"its published numbers to PEAKS") from None


def distinct_ids(sparse: np.ndarray) -> int:
    """Distinct non-PAD ids of a batch."""
    ids = np.asarray(sparse).ravel()
    return int(np.unique(ids[ids >= 0]).size)


def dense_params(cfg: dict, root=None) -> int:
    """Parameters outside the embedding tables, by the configuration's
    kind (``bench/models/<kind>.py``)."""
    return models.load(cfg["kind"], root).dense_params(cfg)


def forward_flops(cfg: dict, rows: int, root=None) -> float:
    """FLOPs of one forward pass over ``rows`` samples, by the
    configuration's kind."""
    return models.load(cfg["kind"], root).forward_flops(cfg, rows)


def train_step(cfg: dict, rows: int, distinct: float, root=None) -> dict:
    """A training step over ``rows`` samples touching ``distinct``
    embedding rows: forward and backward (3x the forward FLOPs), and
    each touched row of every table leaf with its row-wise Adagrad
    accumulator, and each dense parameter, read and written once."""
    kind = models.load(cfg["kind"], root)
    per_row = sum(width + 1 for width in kind.tables(cfg).values())
    table = distinct * per_row                       # rows + accumulators
    dense = kind.dense_params(cfg)
    return {"flops": 3 * kind.forward_flops(cfg, rows),
            "bytes": 2 * F32 * (table + dense)}


def staged_gather(rows_pulled: float, E: int) -> dict:
    """The prefetch pull: each pulled row read from the table and written
    into its slot once."""
    return {"flops": 0.0, "bytes": 2 * F32 * rows_pulled * E}


def least_seconds(work: dict, peak: dict) -> tuple[float, str]:
    """The least time the chip can take for ``work``, and which peak
    bounds it."""
    t_flops = work["flops"] / peak["flops"]
    t_bytes = work["bytes"] / peak["hbm_bytes_per_s"]
    return (t_flops, "flops") if t_flops >= t_bytes else (t_bytes, "hbm")
