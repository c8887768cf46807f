# The work counts as they stood before each model kind moved into its own
# file under bench/models/, kept verbatim (the functions the kinds took
# over): the kind modules are held to them.

F32 = 4


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
