"""Required work of the benchmarked programs, from shapes and ids, and
the peaks of the chips they run on.

"Required" is the least any implementation of the same computation has
to do: each distinct embedding row read and written once, each dense
parameter read and written once, and the model's multiply-adds.  A share built on these counts can therefore not pass
100% for any implementation.
"""
from __future__ import annotations

import numpy as np

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


def _mlp_macs(din, dims) -> int:
    macs = 0
    for d in dims:
        macs += din * d
        din = d
    return macs


def dense_params(cfg: dict) -> int:
    """Parameters outside the embedding tables."""
    E, mlp = int(cfg["embedding_dim"]), tuple(cfg["mlp_dims"])
    F = len(cfg["tables"]["sizes"])
    n_dense = int(cfg["tables"]["n_dense"])
    inter = E * (F + 2) if cfg["kind"] == "dcn" else E
    p = _mlp_macs(n_dense, (*mlp, E)) + _mlp_macs(inter, (*mlp, 1))
    if cfg["kind"] == "dcn":
        p += 2 * int(cfg["cross_layers"]) * inter
    return p


def forward_flops(cfg: dict, rows: int) -> float:
    """FLOPs of one forward pass over ``rows`` samples: the MLPs and the
    interaction (2 per multiply-add), plus the pooling sums."""
    E, mlp = int(cfg["embedding_dim"]), tuple(cfg["mlp_dims"])
    t = cfg["tables"]
    F, H = len(t["sizes"]), int(t["hist_max"])
    macs = _mlp_macs(int(t["n_dense"]), (*mlp, E))
    if cfg["kind"] == "dcn":
        d = E * (F + 2)
        macs += _mlp_macs(d, (*mlp, 1))
        flops = 2 * macs + int(cfg["cross_layers"]) * 5 * d + H * E
    else:
        macs += _mlp_macs(E, (*mlp, 1))
        flops = 2 * macs + (F + H) * E
        if cfg["kind"] == "dfm":
            flops += 3 * (F + 2) * E
    return float(rows) * flops


def train_step(cfg: dict, rows: int, distinct: float) -> dict:
    """A training step over ``rows`` samples touching ``distinct``
    embedding rows: forward and backward (3x the forward FLOPs), and
    each touched row with its row-wise Adagrad accumulator, and each
    dense parameter, read and written once."""
    E = int(cfg["embedding_dim"])
    wide = 1 if cfg["kind"] == "wdl" else 0
    table = distinct * ((E + wide) + (1 + wide))     # rows + accumulators
    dense = dense_params(cfg)
    return {"flops": 3 * forward_flops(cfg, rows),
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
