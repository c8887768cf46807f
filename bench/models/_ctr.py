"""What the CTR kinds (Wide & Deep, DeepFM, DCN) share: one id per field
and then one history bag over field 0, in one (V, E) table; a bottom
MLP from the dense features to E; a top MLP through ``mlp_dims`` to one
logit.  A kind of another layout brings its own helpers."""
import jax.numpy as jnp

from bench import reference as R


def gather_rows(params, sparse):
    """(B, W, E) embedding rows of ``sparse``, zero on PAD."""
    valid = sparse >= 0
    ids = jnp.where(valid, sparse, 0)
    return params["embed"][ids] * valid[..., None].astype(params["embed"].dtype)


def init(model, dtype, ks, inter_dim: int) -> dict:
    """The weights the CTR kinds share, from the keys ``ks``: the (V, E)
    table, the bottom MLP from the dense features to E, and the top MLP
    from ``inter_dim`` through ``mlp_dims`` to one logit."""
    E, dims = model["embedding_dim"], model["mlp_dims"]
    return {"embed": R.normal(ks[0], (model.V, E), 0.01, dtype),
            "bottom": R.mlp_init(ks[1], R.mlp_shapes(model.n_dense,
                                                     (*dims, E)), dtype),
            "top": R.mlp_init(ks[2], R.mlp_shapes(inter_dim, (*dims, 1)),
                              dtype)}


def inputs(model, params, sparse, dense) -> dict:
    """What the CTR kinds' interactions read from a batch of ``sparse``
    (B, W) ids, one per field and then the history bag, and ``dense``:
    ``emb_all`` (B, W, E), every id's row, zero on PAD; ``emb`` (B, F + 1,
    E), the fields' rows and the bag's mean; ``d`` (B, E), the bottom
    MLP's output; ``mean`` (B, E), the mean of every row plus ``d``; and
    the ``valid`` mask and PAD-free ``ids``."""
    F = model.F
    dt = params["embed"].dtype
    valid = sparse >= 0
    ids = jnp.where(valid, sparse, 0)
    emb_all = gather_rows(params, sparse)
    hn = jnp.maximum(valid[:, F:].sum(axis=1, keepdims=True), 1).astype(dt)
    pooled = emb_all[:, F:].sum(axis=1) / hn
    emb = jnp.concatenate([emb_all[:, :F], pooled[:, None]], axis=1)
    d = R.mlp(model, params["bottom"], dense.astype(dt))
    denom = jnp.maximum(valid.sum(axis=1, keepdims=True), 1).astype(dt)
    mean = emb_all.sum(axis=1) / denom + d
    return {"valid": valid, "ids": ids, "emb_all": emb_all, "emb": emb,
            "d": d, "mean": mean}


def _macs(din, dims) -> int:
    macs = 0
    for d in dims:
        macs += din * d
        din = d
    return macs


def mlp_macs(cfg: dict, inter_dim: int) -> int:
    """Multiply-adds of the two MLPs: the bottom one from the dense
    features to E, the top one from ``inter_dim`` through ``mlp_dims``
    to one logit."""
    E, mlp = int(cfg["embedding_dim"]), tuple(cfg["mlp_dims"])
    return (_macs(int(cfg["tables"]["n_dense"]), (*mlp, E))
            + _macs(inter_dim, (*mlp, 1)))
