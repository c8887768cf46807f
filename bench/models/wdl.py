"""Wide & Deep (Cheng et al., arXiv:1606.07792): the deep MLP over the
mean of every embedded id plus the bottom MLP's output, and a wide
linear term of one weight per id."""
import jax

from bench import reference as R
from bench.models import _ctr


def tables(cfg):
    return {"embed": int(cfg["embedding_dim"]), "wide": 1}


def init(model, dtype, key):
    ks = jax.random.split(key, 10)
    p = _ctr.init(model, dtype, ks, model["embedding_dim"])
    p["wide"] = R.normal(ks[3], (model.V, 1), 0.01, dtype)
    return p


def forward(model, params, sparse, dense):
    x = _ctr.inputs(model, params, sparse, dense)
    deep = R.mlp(model, params["top"], x["mean"])[:, 0]
    wide = (params["wide"][x["ids"]][..., 0]
            * x["valid"].astype(params["embed"].dtype)).sum(axis=1)
    return deep + wide


def dense_params(cfg):
    return _ctr.mlp_macs(cfg, int(cfg["embedding_dim"]))


def forward_flops(cfg, rows):
    """The MLPs (2 per multiply-add) and the pooling sums."""
    t = cfg["tables"]
    F, H = len(t["sizes"]), int(t["hist_max"])
    return float(rows) * (2 * dense_params(cfg)
                          + (F + H) * int(cfg["embedding_dim"]))
