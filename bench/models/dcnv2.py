"""DLRM-DCNv2 as MLPerf Training runs it (mlcommons/training
``recommendation_v2/torchrec_dlrm``; DCN-V2, Wang et al.,
arXiv:2008.13535, eq. (2) in the low-rank form of its section 3).

Every field is a bag of ids, sum-pooled (TorchRec ``EmbeddingBagCollection``,
pooling SUM); a sample's row holds the fields' bags one after another,
``tables.bag_sizes`` long each, with no PAD and no history bag.  With B
the batch and E the embedding width:

- ``d = relu(L_k(... relu(L_1(dense))))``, ``bottom_dims`` wide, every
  layer with a bias and a ReLU (``DenseArch``);
- ``e_f`` the sum of field f's bag's rows;
- ``x0 = concat(d, e_1 ... e_F)``, dense first (``InteractionDCNArch``);
- ``x_{l+1} = x0 * (W_l (V_l x_l) + b_l) + x_l`` for ``cross_layers``
  layers, V_l of rank ``cross_rank`` with no bias (``LowRankCrossNet``);
- the logit from the top MLP through ``top_dims``, biases on every layer,
  ReLU on all but the last (``OverArch``); BCE on it.

Departures from TorchRec, as the program under test makes them:

- initialisation: every weight is normal with scale ``fan_in ** -0.5``
  and every bias zero, drawn from the seed as the program draws them (so
  that ``train_cell`` can compare the program's state with these
  weights), not TorchRec's uniform; the table is normal with scale 0.01;
- the optimizer: the program's row-wise Adagrad on every leaf, the dense
  ones too (``bench/reference.py``), where MLPerf trains the dense part
  with Adagrad of its own.

The pooling here is a scatter-add over each column's field, where the
program sums slices: the two agree to float32 rounding.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from bench import reference as R
from bench.traffic import CTRSampler


def tables(cfg):
    return {"embed": int(cfg["embedding_dim"])}


def _widths(cfg, F):
    """The bottom MLP's widths, x0's width (the bottom output and F pooled
    rows; the cross's and the top MLP's input) and the top MLP's widths.
    ``cfg`` is the configuration or a ``reference.Model``."""
    bottom = tuple(cfg["bottom_dims"])
    return (bottom, bottom[-1] + int(cfg["embedding_dim"]) * F,
            tuple(cfg["top_dims"]))


def _layers_init(key, din, dims, dtype):
    layers = R.mlp_init(key, R.mlp_shapes(din, dims), dtype)
    for lp, d in zip(layers, dims):
        lp["b"] = jnp.zeros((d,), dtype)
    return layers


def init(model, dtype, key):
    ks = jax.random.split(key, 10)
    E, r = model["embedding_dim"], model["cross_rank"]
    bottom, d, top = _widths(model, model.F)
    cross = []
    for l in range(model["cross_layers"]):
        kv, kw = jax.random.split(jax.random.fold_in(ks[4], l))
        cross.append({"v": R.normal(kv, (d, r), d ** -0.5, dtype),
                      "w": R.normal(kw, (r, d), r ** -0.5, dtype),
                      "b": jnp.zeros((d,), dtype)})
    return {"embed": R.normal(ks[0], (model.V, E), 0.01, dtype),
            "bottom": _layers_init(ks[1], model.n_dense, bottom, dtype),
            "top": _layers_init(ks[2], d, top, dtype),
            "cross": cross}


def _mlp(model, layers, x, relu_last):
    for i, lp in enumerate(layers):
        x = R.dot(model, x, lp["w"]) + lp["b"]
        if relu_last or i + 1 < len(layers):
            x = jax.nn.relu(x)
    return x


def forward(model, params, sparse, dense):
    bags = dict(model["tables"])["bag_sizes"]
    table = params["embed"]
    dt = table.dtype
    valid = sparse >= 0
    rows = table[jnp.where(valid, sparse, 0)] * valid[..., None].astype(dt)
    field = np.repeat(np.arange(len(bags)), bags)      # each column's field
    pooled = jnp.zeros((rows.shape[0], len(bags), rows.shape[-1]),
                       dt).at[:, field].add(rows)
    d = _mlp(model, params["bottom"], dense.astype(dt), relu_last=True)
    x0 = jnp.concatenate([d, pooled.reshape(pooled.shape[0], -1)], axis=-1)
    x = x0
    for lp in params["cross"]:
        xw = R.dot(model, R.dot(model, x, lp["v"]), lp["w"]) + lp["b"]
        x = x0 * xw + x
    return _mlp(model, params["top"], x, relu_last=False)[:, 0]


def _mlp_counts(din, dims):
    """(weights, biases) of an MLP from ``din`` through ``dims``."""
    return sum(a * b for a, b in R.mlp_shapes(din, dims)), sum(dims)


def dense_params(cfg):
    """The MLPs' weights and biases and each cross layer's V, W and b."""
    bottom, d, top = _widths(cfg, len(cfg["tables"]["sizes"]))
    r, L = int(cfg["cross_rank"]), int(cfg["cross_layers"])
    n_dense = int(cfg["tables"]["n_dense"])
    return (sum(_mlp_counts(n_dense, bottom)) + sum(_mlp_counts(d, top))
            + L * (2 * d * r + d))


def forward_flops(cfg, rows):
    """The MLPs (2 per multiply-add); per cross layer its two low-rank
    products (2 x d x r multiply-adds) and three elementwise passes over
    x0's width (the bias, the product with x0, the residual); and one
    row addition of E per id pooled."""
    bottom, d, top = _widths(cfg, len(cfg["tables"]["sizes"]))
    r, L = int(cfg["cross_rank"]), int(cfg["cross_layers"])
    macs = (_mlp_counts(int(cfg["tables"]["n_dense"]), bottom)[0]
            + _mlp_counts(d, top)[0])
    ids = sum(int(n) for n in cfg["tables"]["bag_sizes"])
    return float(rows) * (2 * macs + L * (2 * 2 * d * r + 3 * d)
                          + ids * int(cfg["embedding_dim"]))


def _program_takes_bags() -> None:
    """The program lays a sample's ids out as its workload's
    ``bag_sizes`` say; a program whose workload type has no such field
    does not know this kind (it would read the first F ids as the
    fields).  It stops here, with a non-zero exit, before anything is
    made."""
    from bench import program

    if "bag_sizes" not in {f.name for f in
                           dataclasses.fields(program.CTRWorkload)}:
        raise SystemExit("unknown model kind 'dcnv2' to the program under "
                         "test: its CTRWorkload has no bag_sizes")


class Sampler(CTRSampler):
    """Sample ids of one configuration: ``bag_sizes[f]`` ids of field f
    for each sample, drawn independently from the field's Zipf over its
    table with ``CTRSampler``'s group locality, no history bag and no
    PAD.  It reproduces the program's stream (``CTRWorkload`` with
    ``bag_sizes``) array for array."""

    def __init__(self, spec: dict):
        _program_takes_bags()
        super().__init__(dict(spec, hist_max=0, hist_mean=0.0))
        self.bag_sizes = tuple(int(n) for n in spec["bag_sizes"])
        if len(self.bag_sizes) != self.n_fields:
            raise ValueError("tables: bag_sizes and sizes differ in length")

    @property
    def width(self) -> int:
        return sum(self.bag_sizes)

    def sparse(self, rng: np.random.Generator, batch: int) -> np.ndarray:
        """(batch, width) flat ids, field by field, each field's bag in
        ``bag_sizes[f]`` columns."""
        groups = rng.integers(0, self.n_groups, batch)
        cols = []
        for f, (size, a, n) in enumerate(zip(self.sizes, self.zipf_a,
                                             self.bag_sizes)):
            ids = self.zipf(rng, a, batch * n, size)
            ids = self._local(rng, a, batch * n, size, np.repeat(groups, n),
                              ids)
            cols.append(ids.reshape(batch, n) + self.offsets[f])
        return np.concatenate(cols, axis=1)
