"""Pallas TPU kernel: one-pass row pack for the ragged exchange.

The ragged executor (repro.exchange.ragged) turns a dispatch assignment
into per-destination send blocks.  The data movement is a gather with
holes: slot ``s`` of the flattened (n * budget, F) send buffer either
takes row ``slot_to_row[s]`` of the local samples or stays PAD.  This
kernel streams ``slot_to_row`` through scalar prefetch and fills the
buffer in blocks of 8 slots: each block starts as PAD in-register (no
separate memset pass over the buffer) and every live slot DMAs its row
from HBM — the row-tile DMA of kernels/emb_lookup, writing rows instead
of pooling them.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .emb_lookup import (DEFAULT_BLOCK_E, ROWS, _auto, _tile_rows,
                         gather_rows_into, row_source)


def _kernel(idx_ref, main_ref, tail_ref, out_ref, buf, sems, *, fill,
            block_e):
    out_ref[...] = jnp.full(out_ref.shape, fill, out_ref.dtype)
    cols = pl.ds(pl.multiple_of(pl.program_id(1) * block_e, block_e),
                 block_e)
    gather_rows_into(out_ref, idx_ref, (main_ref, tail_ref), buf, sems,
                     cols)


def _slots(slot_to_row):
    """(S,) slot map -> int32 padded to whole 8-slot blocks (PAD = -1)."""
    (S,) = slot_to_row.shape
    return jnp.pad(slot_to_row.astype(jnp.int32), (0, (-S) % ROWS),
                   constant_values=-1)


@functools.partial(jax.jit,
                   static_argnames=("fill", "block_e", "interpret"))
def gather_rows_pallas(
    rows: jnp.ndarray,
    slot_to_row: jnp.ndarray,
    *,
    fill: int = -1,
    block_e: int = DEFAULT_BLOCK_E,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """out[s] = rows[slot_to_row[s]] where slot_to_row[s] >= 0, else fill.

    rows: (m, F) of a 32-bit dtype; slot_to_row: (S,) int32 (-1 = PAD
    slot).  Returns (S, F) in rows.dtype.  ``interpret=None``
    auto-selects: compiled on a real TPU backend, interpret mode
    everywhere else.
    """
    m, F = rows.shape
    (S,) = slot_to_row.shape
    idx = _slots(slot_to_row)
    src = row_source(rows, block_e)
    Fp = src[0].shape[1]

    out = pl.pallas_call(
        functools.partial(_kernel, fill=fill, block_e=block_e),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(idx.shape[0] // ROWS, Fp // block_e),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)] * 2,
            out_specs=pl.BlockSpec((ROWS, block_e),
                                   lambda s, e, idx_: (s, e)),
            scratch_shapes=[
                pltpu.VMEM((ROWS, _tile_rows(rows.dtype), block_e),
                           rows.dtype),
                pltpu.SemaphoreType.DMA((ROWS,)),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((idx.shape[0], Fp), rows.dtype),
        interpret=_auto(interpret),
    )(idx, *src)
    return out[:S, :F]


def _quant_kernel(idx_ref, main_ref, tail_ref, codes_ref, meta_ref, buf,
                  sems, *, fill, F, B, G, levels):
    # gather the block's rows (PAD slots = constant ``fill`` rows) ...
    codes_ref[...] = jnp.full(codes_ref.shape, fill, jnp.float32)
    gather_rows_into(codes_ref, idx_ref, (main_ref, tail_ref), buf, sems,
                     pl.ds(0, main_ref.shape[1]))
    row = codes_ref[...]                                  # (ROWS, Fp)
    col = jax.lax.broadcasted_iota(jnp.int32, row.shape, 1)
    lane = jax.lax.broadcasted_iota(jnp.int32, meta_ref.shape, 1)
    scale_cols = jnp.zeros_like(row)
    zp_cols = jnp.zeros_like(row)
    meta = jnp.zeros(meta_ref.shape, jnp.float32)
    # ... then quantize in-register.  G is static — unroll the per-group
    # masked min/max (the pad tail of a partial terminal group and the
    # 128-lane row padding are both excluded by the column mask)
    for g in range(G):
        in_g = (col >= g * B) & (col < min((g + 1) * B, F))
        lo = jnp.min(jnp.where(in_g, row, jnp.inf), axis=1, keepdims=True)
        hi = jnp.max(jnp.where(in_g, row, -jnp.inf), axis=1, keepdims=True)
        sc = (hi - lo) / levels
        sc = jnp.where(sc > 0, sc, 1.0)
        scale_cols = jnp.where(in_g, sc, scale_cols)
        zp_cols = jnp.where(in_g, lo, zp_cols)
        meta = jnp.where(lane == g, sc, jnp.where(lane == G + g, lo, meta))
    meta_ref[...] = meta
    # pad columns divide by the 0-init scale — mask them to code 0
    live = col < F
    codes_ref[...] = jnp.where(
        live,
        jnp.clip(jnp.round((row - zp_cols)
                           / jnp.where(live, scale_cols, 1.0)), 0, levels),
        0.0)


@functools.partial(jax.jit, static_argnames=("codec", "fill", "interpret"))
def gather_rows_quant_pallas(
    rows: jnp.ndarray,
    slot_to_row: jnp.ndarray,
    *,
    codec,
    fill: int = -1,
    interpret: bool | None = None,
):
    """Fused pack + quantize: one pass gathers each send slot's row and
    emits its affine codes plus per-group scale/zero-point.

    rows: (m, F) float32; slot_to_row: (S,) int32 (-1 = PAD slot, which
    quantizes as a constant ``fill`` row — scale 1, zp ``fill``, codes
    0 — so it dequantizes exactly back to ``fill``).  Returns
    ``(codes (S, F) f32-valued ints, scale (S, G) f32, zp (S, G) f32)``
    matching :func:`repro.quant.codecs.quantize_rows` on the gathered
    block (zp exactly; scale up to 1 ULP of backend rounding in the
    ``(hi - lo) / levels`` division, which can flip a boundary code by
    one).  The kernel writes scale and zp side by side into one
    lane-dense metadata block.  fp16 needs no scale pass: it reuses
    :func:`gather_rows_pallas` and casts.
    """
    from ..quant.codecs import get_codec

    c = get_codec(codec)
    if c is None:
        raise ValueError("gather_rows_quant_pallas needs a codec")
    m, F = rows.shape
    (S,) = slot_to_row.shape
    if c.kind == "fp16":
        out = gather_rows_pallas(rows, slot_to_row, fill=fill,
                                 interpret=interpret)
        one = jnp.ones((S, 1), jnp.float32)
        return out.astype(jnp.float16), one, jnp.zeros_like(one)
    B = F if c.block is None else min(c.block, F)
    G = -(-F // B)
    idx = _slots(slot_to_row)
    Sp = idx.shape[0]
    src = row_source(rows.astype(jnp.float32), DEFAULT_BLOCK_E)
    Fp = src[0].shape[1]
    Mp = 2 * G + (-2 * G) % 128

    codes, meta = pl.pallas_call(
        functools.partial(_quant_kernel, fill=fill, F=F, B=B, G=G,
                          levels=c.levels),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(Sp // ROWS,),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)] * 2,
            out_specs=[
                pl.BlockSpec((ROWS, Fp), lambda s, idx_: (s, 0)),
                pl.BlockSpec((ROWS, Mp), lambda s, idx_: (s, 0)),
            ],
            scratch_shapes=[
                pltpu.VMEM((ROWS, _tile_rows(jnp.float32), Fp),
                           jnp.float32),
                pltpu.SemaphoreType.DMA((ROWS,)),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((Sp, Fp), jnp.float32),
            jax.ShapeDtypeStruct((Sp, Mp), jnp.float32),
        ],
        interpret=_auto(interpret),
    )(idx, *src)
    return codes[:S, :F], meta[:S, :G], meta[:S, G:2 * G]
