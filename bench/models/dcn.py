"""Deep & Cross Network (Wang et al., arXiv:1708.05123): ``cross_layers``
vector cross layers over the concatenated fields, history bag mean and
bottom MLP output (E x (F + 2) wide), then the top MLP."""
import jax
import jax.numpy as jnp

from bench import reference as R
from bench.models import _ctr


def tables(cfg):
    return {"embed": int(cfg["embedding_dim"])}


def _inter(model):
    return model["embedding_dim"] * (model.F + 2)


def init(model, dtype, key):
    ks = jax.random.split(key, 10)
    d, L = _inter(model), model["cross_layers"]
    p = _ctr.init(model, dtype, ks, d)
    p["cross_w"] = R.normal(ks[4], (L, d), d ** -0.5, dtype)
    p["cross_b"] = jnp.zeros((L, d), dtype)
    return p


def forward(model, params, sparse, dense):
    x = _ctr.inputs(model, params, sparse, dense)
    emb = x["emb"]
    x0 = jnp.concatenate([emb.reshape(emb.shape[0], -1), x["d"]], axis=-1)
    h = x0
    for l in range(model["cross_layers"]):
        hw = R.dot(model, h, params["cross_w"][l])
        h = x0 * hw[:, None] + params["cross_b"][l][None] + h
    return R.mlp(model, params["top"], h)[:, 0]


def _cross_dim(cfg):
    return int(cfg["embedding_dim"]) * (len(cfg["tables"]["sizes"]) + 2)


def dense_params(cfg):
    d = _cross_dim(cfg)
    return _ctr.mlp_macs(cfg, d) + 2 * int(cfg["cross_layers"]) * d


def forward_flops(cfg, rows):
    """The MLPs (2 per multiply-add), five passes over the cross input
    per cross layer, and the history bag's pooling sum."""
    d, H = _cross_dim(cfg), int(cfg["tables"]["hist_max"])
    return float(rows) * (2 * _ctr.mlp_macs(cfg, d)
                          + int(cfg["cross_layers"]) * 5 * d
                          + H * int(cfg["embedding_dim"]))
