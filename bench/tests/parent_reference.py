# The reference as it stood before each model kind moved into its own file
# under bench/models/, kept verbatim: the kind modules are held to its numbers.
"""Plain reference of the DLRM jobs (WDL, DeepFM, DCN): weights from the
seed, forward pass, BCE loss, gradients and row-wise Adagrad, in
straightforward ``jax.numpy`` on one device.

It follows the published models as the configurations state them and
imports nothing of the program under test.  By default it runs in
float32 at ``highest`` matmul precision (the reference).  Two controls
compute below what the configurations state (float32 storage, matmul
operands rounded to bfloat16 by the TPU's default precision):
``operands=float8_e4m3fn`` rounds every matmul operand to fp8, and
``dtype=bfloat16`` stores and computes everything in bfloat16.  Every
number it hands back is float32.
"""
from __future__ import annotations

import copy
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np


class Model:
    """Static shapes of one configuration: ``cfg`` is the configuration
    file's JSON object."""

    operands = None                 # matmul operand dtype of a control

    def __init__(self, cfg: dict):
        self.kind = cfg["kind"]
        self.E = int(cfg["embedding_dim"])
        self.mlp = tuple(int(d) for d in cfg["mlp_dims"])
        self.cross_layers = int(cfg.get("cross_layers", 0))
        t = cfg["tables"]
        self.F = len(t["sizes"])
        self.V = int(sum(t["sizes"]))
        self.n_dense = int(t["n_dense"])
        if self.kind not in ("wdl", "dfm", "dcn"):
            raise ValueError(f"unknown model kind {self.kind!r}")

    @property
    def inter_dim(self) -> int:
        return self.E * (self.F + 2) if self.kind == "dcn" else self.E

    def with_operands(self, dtype) -> "Model":
        """This model with every matmul operand rounded to ``dtype``."""
        out = copy.copy(self)
        out.operands = dtype
        return out

    def mlp_shapes(self, din, dims):
        out = []
        for d in dims:
            out.append((din, d))
            din = d
        return out


def _mlp_init(key, shapes, dtype):
    return [{"w": (jax.random.normal(jax.random.fold_in(key, i), s,
                                     jnp.float32) * s[0] ** -0.5).astype(dtype)}
            for i, s in enumerate(shapes)]


def init_params(model: Model, seed: int, dtype=jnp.float32):
    """The weights of ``seed``, in one jitted call on the default device."""
    return _init(model, dtype, jax.random.key(seed))


@partial(jax.jit, static_argnums=(0, 1))
def _init(model: Model, dtype, key):
    V, E = model.V, model.E
    ks = jax.random.split(key, 10)
    p = {"embed": (jax.random.normal(ks[0], (V, E), jnp.float32)
                   * 0.01).astype(dtype),
         "bottom": _mlp_init(ks[1], model.mlp_shapes(model.n_dense,
                                                     (*model.mlp, E)), dtype),
         "top": _mlp_init(ks[2], model.mlp_shapes(model.inter_dim,
                                                  (*model.mlp, 1)), dtype)}
    if model.kind == "wdl":
        p["wide"] = (jax.random.normal(ks[3], (V, 1), jnp.float32)
                     * 0.01).astype(dtype)
    if model.kind == "dcn":
        d = model.inter_dim
        p["cross_w"] = (jax.random.normal(ks[4], (model.cross_layers, d),
                                          jnp.float32) * d ** -0.5).astype(dtype)
        p["cross_b"] = jnp.zeros((model.cross_layers, d), dtype)
    return p


def _dot(model, x, w):
    if model.operands is None:
        return x @ w
    r = lambda a: a.astype(model.operands).astype(a.dtype)
    return r(x) @ r(w)


def _mlp(model, layers, x):
    for i, lp in enumerate(layers):
        x = _dot(model, x, lp["w"])
        if i + 1 < len(layers):
            x = jax.nn.relu(x)
    return x


def gather_rows(params, sparse):
    """(B, W, E) embedding rows of ``sparse``, zero on PAD."""
    valid = sparse >= 0
    ids = jnp.where(valid, sparse, 0)
    return params["embed"][ids] * valid[..., None].astype(params["embed"].dtype)


def forward(model: Model, params, sparse, dense):
    """Logits (B,) of a batch: ``sparse`` (B, W) flat ids (PAD = -1),
    ``dense`` (B, n_dense)."""
    F = model.F
    dt = params["embed"].dtype
    valid = sparse >= 0
    ids = jnp.where(valid, sparse, 0)
    emb_all = gather_rows(params, sparse)
    hn = jnp.maximum(valid[:, F:].sum(axis=1, keepdims=True), 1).astype(dt)
    pooled = emb_all[:, F:].sum(axis=1) / hn
    emb = jnp.concatenate([emb_all[:, :F], pooled[:, None]], axis=1)
    d = _mlp(model, params["bottom"], dense.astype(dt))
    denom = jnp.maximum(valid.sum(axis=1, keepdims=True), 1).astype(dt)
    mean = emb_all.sum(axis=1) / denom + d
    if model.kind == "wdl":
        deep = _mlp(model, params["top"], mean)[:, 0]
        wide = (params["wide"][ids][..., 0] * valid.astype(dt)).sum(axis=1)
        return deep + wide
    if model.kind == "dfm":
        feats = jnp.concatenate([emb, d[:, None, :]], axis=1)
        s = feats.sum(axis=1)
        fm = 0.5 * (s * s - (feats * feats).sum(axis=1)).sum(axis=-1)
        first = emb_all.sum(axis=(1, 2))
        deep = _mlp(model, params["top"], mean)[:, 0]
        return deep + fm + first
    x0 = jnp.concatenate([emb.reshape(emb.shape[0], -1), d], axis=-1)
    x = x0
    for l in range(model.cross_layers):
        xw = _dot(model, x, params["cross_w"][l])
        x = x0 * xw[:, None] + params["cross_b"][l][None] + x
    return _mlp(model, params["top"], x)[:, 0]


def bce(logits, labels):
    return jnp.mean(jnp.maximum(logits, 0) - logits * labels
                    + jnp.log1p(jnp.exp(-jnp.abs(logits))))


def adagrad(params, grads, acc, lr, eps=1e-10):
    """Row-wise Adagrad: one accumulator per row (all but the last axis)
    of a parameter of rank >= 2, one per element of a vector."""
    def step(p, g, a):
        if p.ndim >= 2:
            a = a + jnp.mean(jnp.square(g), axis=-1)
            return p - lr * g * jax.lax.rsqrt(a + eps)[..., None], a
        a = a + jnp.square(g)
        return p - lr * g * jax.lax.rsqrt(a + eps), a
    out = jax.tree.map(step, params, grads, acc)
    is_pair = lambda t: isinstance(t, tuple)
    return (jax.tree.map(lambda t: t[0], out, is_leaf=is_pair),
            jax.tree.map(lambda t: t[1], out, is_leaf=is_pair))


def adagrad_init(params):
    return jax.tree.map(lambda p: jnp.zeros(p.shape[:-1] if p.ndim >= 2
                                            else p.shape, p.dtype), params)


def leaf_norms(tree) -> dict[str, jax.Array]:
    """float32 L2 norm of every leaf, by leaf path."""
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    return {jax.tree_util.keystr(k): jnp.sqrt(jnp.sum(
        jnp.square(v.astype(jnp.float32)))) for k, v in flat}


TABLES = ("embed", "wide")          # the (V, .) leaves: one row per id


def touched_rows(batches) -> np.ndarray:
    """Sorted distinct ids of ``batches``: the only table rows that
    training on them can move."""
    ids = np.concatenate([np.asarray(b[0]).ravel() for b in batches])
    return np.unique(ids[ids >= 0])


def _precision(dtype):
    return "highest" if dtype == jnp.float32 else "default"


def _host(tree) -> dict:
    return {k: float(v) for k, v in tree.items()}


def train_steps(model: Model, seed: int, batches, lr: float,
                dtype=jnp.float32, keep: float = 1.0, operands=None) -> dict:
    """Run the reference through ``batches`` (a list of (sparse, dense,
    labels) host arrays, each the whole step's batch) from the weights of
    ``seed``, stored in ``dtype``.

    Only the table rows that the batches touch are trained: every other
    row has a zero gradient, which row-wise Adagrad turns into no change,
    so it stays as stored.  The whole tables are made once from the seed,
    to take those rows and to measure, where ``dtype`` is below float32,
    how far the stored tables lie from the float32 weights on the other
    rows (their drift).

    Returns host values: the loss of every step; per leaf, the norm of
    the first step's gradient and of the change from the seed's float32
    weights after the first step and after all of them; and per table
    the drift (``drift``, 0 in float32).  ``keep < 1`` trains each step
    on its first ``keep`` share of rows only (a planted fault)."""
    if operands is not None:
        model = model.with_operands(operands)
    rows = touched_rows(batches)
    with jax.default_matmul_precision(_precision(dtype)):
        full = init_params(model, seed)
        drift = {f"['{k}']": 0.0 if dtype == jnp.float32 else
                 float(_drift(full[k], jnp.asarray(rows), dtype))
                 for k in TABLES if k in full}
        p0 = dict(full)
        for k in TABLES:
            if k in full:
                p0[k] = full[k][jnp.asarray(rows)]
        del full
        p = jax.tree.map(lambda a: a.astype(dtype), p0)
        acc = adagrad_init(p)
        losses = []
        for i, (sparse, dense, labels) in enumerate(batches):
            n = max(1, int(round(len(labels) * keep)))
            sp = np.where(sparse[:n] >= 0,
                          np.searchsorted(rows, sparse[:n]), -1)
            loss, grads, p, acc = _ref_step(
                model, lr, p, acc, jnp.asarray(sp, jnp.int32),
                jnp.asarray(dense[:n]), jnp.asarray(labels[:n], dtype))
            losses.append(float(loss))
            if i == 0:
                g_norms = _host(leaf_norms(grads))
                d1_norms = _change(p, p0, drift)
            del grads
        d_norms = _change(p, p0, drift)
    return {"loss": losses, "grad_norm": g_norms, "delta1_norm": d1_norms,
            "delta_norm": d_norms, "drift": drift}


@partial(jax.jit, static_argnums=(0, 1))
def _ref_step(model, lr, p, acc, sparse, dense, labels):
    loss, grads = jax.value_and_grad(
        lambda q: bce(forward(model, q, sparse, dense), labels))(p)
    p2, acc2 = adagrad(p, grads, acc, lr)
    return loss.astype(jnp.float32), grads, p2, acc2


@partial(jax.jit, static_argnums=(2,))
def _drift(table, rows, dtype):
    """Norm of ``table`` stored in ``dtype`` less ``table``, over the
    rows not in ``rows``."""
    d = table.astype(dtype).astype(jnp.float32) - table
    per_row = jnp.sum(jnp.square(d), axis=-1).at[rows].set(0.0)
    return jnp.sqrt(jnp.sum(per_row))


def _change(p, p0, drift) -> dict:
    """Per leaf, the norm of ``p`` less the float32 weights ``p0``
    (tables at the touched rows), with the tables' drift on the rest."""
    out = _host(leaf_norms(jax.tree.map(
        lambda a, b: a.astype(jnp.float32) - b, p, p0)))
    return {k: float(np.hypot(v, drift.get(k, 0.0))) for k, v in out.items()}
