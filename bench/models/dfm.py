"""DeepFM (Guo et al., arXiv:1703.04247): the deep MLP of Wide & Deep,
a factorization machine's second-order term over the fields, the
history bag's mean and the bottom MLP's output, and a first-order sum
of every embedded id."""
import jax
import jax.numpy as jnp

from bench import reference as R
from bench.models import _ctr


def tables(cfg):
    return {"embed": int(cfg["embedding_dim"])}


def init(model, dtype, key):
    ks = jax.random.split(key, 10)
    return _ctr.init(model, dtype, ks, model["embedding_dim"])


def forward(model, params, sparse, dense):
    x = _ctr.inputs(model, params, sparse, dense)
    feats = jnp.concatenate([x["emb"], x["d"][:, None, :]], axis=1)
    s = feats.sum(axis=1)
    fm = 0.5 * (s * s - (feats * feats).sum(axis=1)).sum(axis=-1)
    first = x["emb_all"].sum(axis=(1, 2))
    deep = R.mlp(model, params["top"], x["mean"])[:, 0]
    return deep + fm + first


def dense_params(cfg):
    return _ctr.mlp_macs(cfg, int(cfg["embedding_dim"]))


def forward_flops(cfg, rows):
    """The MLPs (2 per multiply-add), the pooling sums and the FM's
    three passes over the F + 2 feature rows."""
    E, t = int(cfg["embedding_dim"]), cfg["tables"]
    F, H = len(t["sizes"]), int(t["hist_max"])
    return float(rows) * (2 * dense_params(cfg) + (F + H) * E
                          + 3 * (F + 2) * E)
