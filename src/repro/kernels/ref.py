"""Pure-jnp oracles for every Pallas kernel (the allclose targets)."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def _pool(rows, ids, weights):
    """Fold (B, F, E) looked-up rows over the fields in order (PAD ids,
    -1, weigh 0): the summation order of the Pallas pooled kernels."""
    B, F = ids.shape
    if weights is None:
        weights = jnp.ones((B, F), jnp.float32)
    w = jnp.where(ids >= 0, weights, 0.0).astype(jnp.float32)
    acc = jnp.zeros((B, rows.shape[-1]), jnp.float32)
    for f in range(F):
        acc = acc + rows[:, f].astype(jnp.float32) * w[:, f, None]
    return acc


def pooled_lookup_ref(table, ids, weights=None):
    """sum_f table[ids[b,f]] * w[b,f]; PAD = -1."""
    return _pool(table[jnp.maximum(ids, 0)], ids, weights)


def pooled_lookup_staged_ref(plane_rows, table, slots, ids, weights=None):
    """Pooled lookup reading ``plane_rows[slots]`` where ``slots >= 0``
    and ``table[ids]`` elsewhere; PAD ids = -1."""
    from_plane = plane_rows[jnp.maximum(slots, 0)].astype(table.dtype)
    rows = jnp.where((slots >= 0)[..., None], from_plane,
                     table[jnp.maximum(ids, 0)])
    return _pool(rows, ids, weights)


def pooled_lookup_quant_ref(codes, scale, zp, ids, codec, weights=None):
    """Pooled lookup over the dequantized table ``codes * scale + zp``."""
    from ..quant.codecs import dequantize_rows
    return pooled_lookup_ref(dequantize_rows(codes, scale, zp, codec), ids,
                             weights)


def gather_rows_ref(rows, slot_to_row, fill=-1):
    """out[s] = rows[slot_to_row[s]] where slot_to_row[s] >= 0, else fill."""
    got = rows[jnp.maximum(slot_to_row, 0)]
    return jnp.where((slot_to_row >= 0)[:, None], got,
                     jnp.asarray(fill, rows.dtype))


def staged_gather_ref(plane_rows, table, src_rows):
    """out[s] = table[src_rows[s]] if src_rows[s] >= 0 else plane_rows[s]."""
    return jnp.where((src_rows >= 0)[:, None],
                     table[jnp.maximum(src_rows, 0)], plane_rows)


def gather_rows_quant_ref(rows, slot_to_row, codec, fill=-1):
    """Gathered rows (PAD = constant ``fill`` rows) through
    :func:`repro.quant.codecs.quantize_rows`."""
    from ..quant.codecs import quantize_rows
    return quantize_rows(gather_rows_ref(rows.astype(jnp.float32),
                                         slot_to_row, fill), codec)


def auction_bids_ref(cost, min_price, unassigned, eps):
    """Row-parallel bid phase of the auction round (core/auction.py).

    cost: (k, n); min_price: (n,); unassigned: (k,) bool.
    Returns best_j (k,) int32, bid (k,) f32 (NEG for assigned rows).
    """
    NEG = -1e30
    k, n = cost.shape
    values = -cost - min_price[None, :]
    best_j = jnp.argmax(values, axis=1)
    w1 = jnp.max(values, axis=1)
    v2 = values.at[jnp.arange(k), best_j].set(NEG)
    w2 = jnp.max(v2, axis=1)
    w2 = jnp.where(n == 1, w1, w2)
    bid = min_price[best_j] + (w1 - w2) + eps
    bid = jnp.where(unassigned, bid, NEG)
    return best_j.astype(jnp.int32), bid.astype(jnp.float32)


def flash_attention_ref(q, k, v, causal=True, window=0):
    """Naive softmax attention oracle.  q: (B,Sq,KV,G,hd), k/v: (B,Sk,KV,hd)."""
    B, Sq, KV, G, hd = q.shape
    Sk = k.shape[1]
    logits = jnp.einsum("bskgh,btkh->bkgst", q.astype(jnp.float32),
                        k.astype(jnp.float32)) / np.sqrt(hd)
    qp = jnp.arange(Sq)[:, None]
    kp = jnp.arange(Sk)[None, :]
    mask = jnp.ones((Sq, Sk), bool)
    if causal:
        mask &= kp <= qp
    if window:
        mask &= qp - kp < window
    logits = jnp.where(mask, logits, -1e30)
    p = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bkgst,btkh->bskgh", p, v.astype(jnp.float32))
