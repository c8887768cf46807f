"""DLRM models for the paper's workloads: WDL [12], DeepFM [24], DCN [66],
and DLRM-DCNv2 as MLPerf Training runs it.

One flat embedding table over the concatenated field vocabularies (ids are
pre-offset by the data pipeline) — exactly the "global embedding table"
that the PS holds in the paper; the ESD layer manages which rows live in
which worker cache.  Dense features go through the bottom MLP; interaction
is model-specific (wide linear / FM / cross network / low-rank cross
network); top MLP emits the CTR logit.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ..configs.dlrm_configs import DLRMConfig
from ..data.synthetic import CTRWorkload
from .layers import init_linear, linear


def init_params(key, cfg: DLRMConfig, workload: CTRWorkload):
    V, E = workload.vocab, cfg.embedding_dim
    F = workload.n_fields
    ks = jax.random.split(key, 10)
    bias = cfg.kind == "dcnv2"
    p = {
        "embed": jax.random.normal(ks[0], (V, E), jnp.float32) * 0.01,
        "bottom": _init_mlp(ks[1], workload.n_dense, cfg.bottom_widths, bias),
    }
    # interaction blocks: F single-hot fields + 1 pooled multi-hot history
    # bag + 1 dense projection; dcnv2: the bottom MLP's output + F pooled
    # bags
    inter_dim = {"wdl": E, "dfm": E, "dcn": E * (F + 2),
                 "dcnv2": cfg.bottom_widths[-1] + E * F}[cfg.kind]
    p["top"] = _init_mlp(ks[2], inter_dim, cfg.top_widths, bias)
    if cfg.kind == "wdl":
        p["wide"] = jax.random.normal(ks[3], (V, 1), jnp.float32) * 0.01
    if cfg.kind == "dcn":
        d = E * (F + 2)
        p["cross_w"] = jax.random.normal(ks[4], (cfg.cross_layers, d), jnp.float32) * (d ** -0.5)
        p["cross_b"] = jnp.zeros((cfg.cross_layers, d), jnp.float32)
    if cfg.kind == "dcnv2":
        # layer l: V (d -> r), W (r -> d) and the bias of W's output
        p["cross"] = []
        for l in range(cfg.cross_layers):
            kv, kw = jax.random.split(jax.random.fold_in(ks[4], l))
            p["cross"].append({
                "v": init_linear(kv, inter_dim, cfg.cross_rank, jnp.float32)["w"],
                "w": init_linear(kw, cfg.cross_rank, inter_dim, jnp.float32)["w"],
                "b": jnp.zeros((inter_dim,), jnp.float32)})
    return p


def _flat_table(tbl):
    """A lookup view of a table: PS-stacked (n_ps, max_rows, E) flattens
    so PS-linearized ids index it directly; flat (V, E) passes through."""
    return tbl.reshape(-1, tbl.shape[-1]) if tbl.ndim == 3 else tbl


def ps_stack_tables(params, part):
    """Re-home the flat (V, ...) tables onto ``part.n_ps`` parameter
    servers: rows permute into the repro.ps (shard, local_row) layout and
    stack to (n_ps, max_rows, ...) (padding rows zero, never gathered —
    lookups use PS-linearized ids against the flattened stack)."""
    out = dict(params)
    lin = np.asarray(part.to_linear(np.arange(part.vocab)))
    for name in ("embed", "wide"):
        if name not in params:
            continue
        tbl = params[name]
        full = jnp.zeros((part.linear_size, tbl.shape[1]), tbl.dtype)
        out[name] = full.at[lin].set(tbl).reshape(
            part.n_ps, part.max_rows, tbl.shape[1])
    return out


def _init_mlp(key, din, dims, bias=False):
    layers = []
    for i, dout in enumerate(dims):
        layers.append(init_linear(jax.random.fold_in(key, i), din, dout,
                                  jnp.float32))
        if bias:
            layers[-1]["b"] = jnp.zeros((dout,), jnp.float32)
        din = dout
    return layers


def _mlp(layers, x):
    """ReLU between the layers, none after the last; a layer with ``b``
    adds it."""
    for i, lp in enumerate(layers):
        x = linear(lp, x)
        if "b" in lp:
            x = x + lp["b"]
        if i + 1 < len(layers):
            x = jax.nn.relu(x)
    return x


def bag_pool(rows, bags):
    """Sum-pool (B, sum(bags), E) rows into (B, len(bags), E): field f's
    bag is the next ``bags[f]`` columns (a static layout)."""
    out, o = [], 0
    for n in bags:
        out.append(rows[:, o:o + n].sum(axis=1))
        o += n
    return jnp.stack(out, axis=1)


def _dcnv2(params, bags, sparse_ids, dense, emb_all):
    """DLRM-DCNv2 (TorchRec DLRM_DCN): every field's bag sum-pooled; x0 the
    bottom MLP's output (ReLU on every layer) then the F pooled rows;
    ``x_{l+1} = x0 * (W_l (V_l x_l) + b_l) + x_l``; the top MLP's logit."""
    with jax.named_scope("dlrm.bag_pool"):
        if emb_all is None:
            valid = sparse_ids >= 0
            ids = jnp.where(valid, sparse_ids, 0)
            emb_all = _flat_table(params["embed"])[ids] * valid[..., None]
        pooled = bag_pool(emb_all, bags)               # (B, F, E)
    d = jax.nn.relu(_mlp(params["bottom"], dense))
    x0 = jnp.concatenate([d, pooled.reshape(pooled.shape[0], -1)], axis=-1)
    x = x0
    with jax.named_scope("dlrm.cross"):
        for lp in params["cross"]:
            x = x0 * ((x @ lp["v"]) @ lp["w"] + lp["b"]) + x
    return _mlp(params["top"], x)[:, 0]


def forward(params, cfg: DLRMConfig, sparse_ids, dense, n_fields=None,
            emb_all=None):
    """sparse_ids: (B, W) flat ids (W = fixed fields + multi-hot history
    slots, PAD=-1; ``dcnv2``: the fields' bags, ``CTRWorkload.bag_sizes``);
    dense: (B, n_dense) -> logits (B,).

    Multi-PS: the tables may arrive PS-stacked as (n_ps, max_rows, ...)
    (repro.ps convention) with ids already PS-linearized — the stack
    flattens so row ``p * max_rows + local`` is PS ``p``'s ``local`` row.

    ``emb_all`` injects pre-gathered (B, W, E) embedding rows (PAD rows
    already zeroed) in place of the canonical-table gather — the serving
    path (repro.serve.step) reads rows from its TTL cache plane and runs
    the identical interaction stack; ``None`` keeps the training gather
    bitwise.
    """
    from ..data.synthetic import WORKLOADS
    if cfg.kind == "dcnv2":
        return _dcnv2(params, WORKLOADS[cfg.workload].bags, sparse_ids, dense,
                      emb_all)
    F = n_fields if n_fields is not None else WORKLOADS[cfg.workload].n_fields
    F = min(F, sparse_ids.shape[1])
    valid = sparse_ids >= 0
    ids = jnp.where(valid, sparse_ids, 0)
    if emb_all is None:
        emb_all = _flat_table(params["embed"])[ids] * valid[..., None]  # (B, W, E)
    # interaction blocks: fields as-is, history mean-pooled into one block
    fields = emb_all[:, :F]
    hist = emb_all[:, F:]
    hn = jnp.maximum(valid[:, F:].sum(axis=1, keepdims=True), 1)
    pooled = hist.sum(axis=1) / hn                     # (B, E)
    emb = jnp.concatenate([fields, pooled[:, None]], axis=1)  # (B, F+1, E)
    d = _mlp(params["bottom"], dense)                  # (B, E)

    denom = jnp.maximum(valid.sum(axis=1, keepdims=True), 1)
    if cfg.kind == "wdl":
        deep_in = emb_all.sum(axis=1) / denom + d
        deep = _mlp(params["top"], deep_in)[:, 0]
        wide = (_flat_table(params["wide"])[ids][..., 0] * valid).sum(axis=1)
        return deep + wide
    if cfg.kind == "dfm":
        # FM second-order via the sum-square trick (fields + pooled + dense)
        feats = jnp.concatenate([emb, d[:, None, :]], axis=1)  # (B, F+2, E)
        s = feats.sum(axis=1)
        fm = 0.5 * (s * s - (feats * feats).sum(axis=1)).sum(axis=-1)
        first = emb_all.sum(axis=(1, 2))
        deep = _mlp(params["top"], emb_all.sum(axis=1) / denom + d)[:, 0]
        return deep + fm + first
    if cfg.kind == "dcn":
        x0 = jnp.concatenate([emb.reshape(emb.shape[0], -1), d], axis=-1)
        x = x0
        with jax.named_scope("dlrm.cross"):
            for l in range(cfg.cross_layers):
                xw = x @ params["cross_w"][l]          # (B,)
                x = x0 * xw[:, None] + params["cross_b"][l][None] + x
        return _mlp(params["top"], x)[:, 0]
    raise ValueError(cfg.kind)


def bce_loss(params, cfg: DLRMConfig, sparse_ids, dense, labels):
    logits = forward(params, cfg, sparse_ids, dense)
    return jnp.mean(
        jnp.maximum(logits, 0) - logits * labels + jnp.log1p(jnp.exp(-jnp.abs(logits)))
    )


def bce_loss_masked(params, cfg: DLRMConfig, sparse_ids, dense, labels):
    """PAD-masked BCE for uneven ragged batches (``cap_slack > 0``).

    The ragged exchange compacts each worker's real samples to the front
    of a fixed (n * budget)-row buffer and fills the rest with PAD
    (labels = -1); those rows contribute neither loss nor gradient, and
    the mean runs over the valid rows only — so the global loss is still
    the mean over the k real samples of the iteration.  On an all-valid
    batch this equals :func:`bce_loss`.
    """
    valid = labels >= 0.0
    logits = forward(params, cfg, sparse_ids, dense)
    lbl = jnp.where(valid, labels, 0.0)
    per_row = (jnp.maximum(logits, 0) - logits * lbl
               + jnp.log1p(jnp.exp(-jnp.abs(logits))))
    per_row = jnp.where(valid, per_row, 0.0)
    return per_row.sum() / jnp.maximum(valid.sum(), 1).astype(per_row.dtype)


def train_step(params, cfg: DLRMConfig, batch, lr=1e-2):
    """Plain-SGD step (the paper's consistency analysis assumes SGD)."""
    loss, grads = jax.value_and_grad(bce_loss)(
        params, cfg, batch["sparse"], batch["dense"], batch["labels"]
    )
    new = jax.tree.map(lambda p, g: p - lr * g, params, grads)
    return new, loss
