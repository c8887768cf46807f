"""Pallas TPU kernels: pooled embedding gather-sum and staged row gathers.

The DLRM hot-spot — for each sample (bag) of F ids, fetch F rows of the
embedding table and sum them — AND, via the Alg.-1 identity (core/cost.py),
the ESD expected-cost matrix itself: with ``table = per_id_cost_rows()``
(V, n) and bags = samples, the pooled sum IS the cost matrix C.  The
sparse engine serves the same kernel a compact (U, n) table holding only
the batch's touched ids (kernels/ops.cost_matrix_pallas_sparse), so the
kernel never sees the vocabulary.

TPU shape of the gather.  Every table stays in HBM (``memory_space=ANY``)
and rows arrive by manual DMA.  A 2-D f32 array in HBM is laid out in
(8, 128) tiles, and a DMA moves whole sublane tiles, so each row fetch
copies the 8-row tile that holds the row (:func:`row_dma`) and the
kernel picks the row out of VMEM by its sublane offset.  A partial last
tile is read from a one-tile copy of it (:func:`row_source`), so no
operand is ever padded or copied whole.  Each grid step owns an
output block of ``ROWS`` = 8 rows x ``block_e`` lanes and launches all of
its row fetches before it waits on any of them; PAD ids (-1) issue no DMA
and contribute exact zeros.  Ids and weights ride scalar prefetch,
flattened to 1-D so SMEM holds them unpadded.  Rows accumulate in field
order, so the pooled sum is the sequential sum of
:func:`repro.kernels.ref.pooled_lookup_ref`.

:func:`staged_gather` is the window-driven prefetch companion
(repro.pipeline.prefetch): one pass over the staging plane that pulls
each freshly selected slot's row straight from the table and carries
every other slot through — the async pull and the merge into the cache
plane fused into a single kernel, no host round-trip.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLOCK_E = 128
ROWS = 8      # output rows per grid step: one 32-bit sublane tile


def _tile_rows(dtype) -> int:
    """Rows per HBM tile of a 2-D ``dtype`` array (8 for 32-bit types):
    the granule one row DMA moves."""
    return 8 * max(1, 4 // jnp.dtype(dtype).itemsize)


def _pad_lanes(x, lanes: int):
    """Zero-pad a 2-D array's rows to a multiple of ``lanes`` (no copy
    when they already are, as at the paper's widths)."""
    pad = (-x.shape[1]) % lanes
    return jnp.pad(x, ((0, 0), (0, pad))) if pad else x


def row_source(x, lanes: int):
    """Split a 2-D array into the two HBM operands its row DMAs read:
    ``(main, tail)``.  ``main`` is ``x`` itself (lane-padded, see
    :func:`_pad_lanes`) and holds every whole row tile; ``tail`` is the partial
    last tile, zero-padded to one whole tile (all zeros when the row
    count is a multiple of the tile)."""
    T = _tile_rows(x.dtype)
    main = _pad_lanes(x, lanes)
    n = x.shape[0] // T * T
    return main, jnp.pad(main[n:], ((0, T - (x.shape[0] - n)), (0, 0)))


def row_dma(src, row, cols, buf, sem):
    """The DMA that brings the tile holding ``row`` of the
    :func:`row_source` pair ``src = (main, tail)`` (lanes ``cols``) into
    the VMEM tile ``buf``: ``(copies, offset)`` with ``copies`` a list of
    ``(predicate, copy)`` of which exactly one predicate holds, and
    ``buf[offset]`` the row once it landed.  Descriptors are pure, so
    the issuing loop and the waiting loop each build their own."""
    main, tail = src
    T = buf.shape[0]
    n_tiles = main.shape[0] // T
    copies = [(row >= n_tiles * T,
               pltpu.make_async_copy(tail.at[:, cols], buf, sem))]
    off = row - n_tiles * T
    if n_tiles:
        start = pl.multiple_of(jnp.minimum(row // T, n_tiles - 1) * T, T)
        in_main = row < n_tiles * T
        copies.append((in_main, pltpu.make_async_copy(
            main.at[pl.ds(start, T), cols], buf, sem)))
        off = jnp.where(in_main, row - start, off)
    return copies, off


def _start(copies, live):
    for pred, cp in copies:
        pl.when(live & pred)(cp.start)


def _wait(copies, live):
    for pred, cp in copies:
        pl.when(live & pred)(cp.wait)


def gather_rows_into(out_ref, idx_ref, src, buf, sems, cols):
    """Overwrite row r of the (ROWS, w) block ``out_ref`` with
    ``src[idx[s0 + r], cols]`` wherever that index is >= 0 (s0 = first
    row of this grid step, ``src`` a :func:`row_source` pair); rows with
    a negative index keep their value.  ``buf`` is a (ROWS, T, w) VMEM
    scratch, ``sems`` ROWS DMA semaphores.  Every row's DMA is issued
    before the first one is waited on."""
    s0 = pl.program_id(0) * ROWS

    def dma(r):
        idx = idx_ref[s0 + r]
        copies, off = row_dma(src, jnp.maximum(idx, 0), cols, buf.at[r],
                              sems.at[r])
        return idx >= 0, copies, off

    @pl.loop(0, ROWS)
    def _issue(r):
        live, copies, _ = dma(r)
        _start(copies, live)

    @pl.loop(0, ROWS)
    def _land(r):
        live, copies, off = dma(r)
        _wait(copies, live)

        @pl.when(live)
        def _():
            out_ref[pl.ds(r, 1), :] = buf[r, pl.ds(off, 1), :]


def _flat_ids(ids, weights, Bp: int, Fp: int):
    """(B, F) ids/weights -> flattened (Bp * Fp,) int32 ids (PAD = -1)
    and f32 weights (0 on PAD), padded with PAD bags and fields."""
    B, F = ids.shape
    if weights is None:
        weights = jnp.ones((B, F), jnp.float32)
    valid = ids >= 0
    ids = jnp.where(valid, ids, -1).astype(jnp.int32)
    w = jnp.where(valid, weights, 0.0).astype(jnp.float32)
    pad = ((0, Bp - B), (0, Fp - F))
    return (jnp.pad(ids, pad, constant_values=-1).reshape(-1),
            jnp.pad(w, pad).reshape(-1))


def _pooled_kernel(*refs, F: int, block_f: int, block_e: int, staged: bool):
    if staged:
        slots_ref, ids_ref, w_ref, *srcs, out_ref, buf, sems = refs
        plane, table = (srcs[0], srcs[1]), (srcs[2], srcs[3])
    else:
        ids_ref, w_ref, *table, out_ref, buf, sems = refs
    b0 = pl.program_id(0) * ROWS
    cols = pl.ds(pl.multiple_of(pl.program_id(1) * block_e, block_e),
                 block_e)
    f0 = pl.program_id(2) * block_f

    @pl.when(pl.program_id(2) == 0)
    def _zero():
        out_ref[...] = jnp.zeros_like(out_ref)

    def dma(j):
        """Lookup j of the step (field-major: j = i * ROWS + r) ->
        (live, [(live-predicate, copies)...], offset, r, flat index)."""
        i, r = j // ROWS, j % ROWS
        k = (b0 + r) * F + f0 + i
        idx = ids_ref[k]
        live = idx >= 0
        dst = (cols, buf.at[i, r], sems.at[i, r])
        copies, off = row_dma(table, jnp.maximum(idx, 0), *dst)
        if not staged:
            return live, [(live, copies)], off, i, r, k
        # a live staging slot answers the lookup; the table only serves
        # plane misses
        slot = slots_ref[k]
        use_plane = live & (slot >= 0)
        pcopies, poff = row_dma(plane, jnp.maximum(slot, 0), *dst)
        return (live, [(use_plane, pcopies), (live & ~use_plane, copies)],
                jnp.where(use_plane, poff, off), i, r, k)

    # issue the step's whole tile of row fetches before waiting on any
    @pl.loop(0, block_f * ROWS)
    def _issue(j):
        for pred, copies in dma(j)[1]:
            _start(copies, pred)

    # accumulate in field order (bitwise the sequential pooled sum)
    @pl.loop(0, block_f * ROWS)
    def _land(j):
        live, sources, off, i, r, k = dma(j)
        for pred, copies in sources:
            _wait(copies, pred)
        row = buf[i, r, pl.ds(off, 1), :].astype(jnp.float32)
        out_ref[pl.ds(r, 1), :] += jnp.where(live, row, 0.0) * w_ref[k]


def _pooled_call(ids, weights, table, plane_rows, slots, *, block_e: int,
                 block_f: int, interpret):
    B, F = ids.shape
    E = table.shape[1]
    block_f = min(block_f, F)
    Bp = B + (-B) % ROWS
    Fp = F + (-F) % block_f
    ids_f, w_f = _flat_ids(ids, weights, Bp, Fp)
    scalars = [ids_f, w_f]
    arrays = [*row_source(table, block_e)]
    if plane_rows is not None:
        sl = jnp.pad(jnp.asarray(slots).astype(jnp.int32),
                     ((0, Bp - B), (0, Fp - F)), constant_values=-1)
        scalars.insert(0, sl.reshape(-1))
        arrays[:0] = row_source(plane_rows.astype(table.dtype), block_e)
    Ep = arrays[0].shape[1]
    out = pl.pallas_call(
        functools.partial(_pooled_kernel, F=Fp, block_f=block_f,
                          block_e=block_e, staged=plane_rows is not None),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(scalars),
            grid=(Bp // ROWS, Ep // block_e, Fp // block_f),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)] * len(arrays),
            out_specs=pl.BlockSpec((ROWS, block_e),
                                   lambda b, e, f, *_: (b, e)),
            scratch_shapes=[
                pltpu.VMEM((block_f, ROWS, _tile_rows(table.dtype),
                            block_e), table.dtype),
                pltpu.SemaphoreType.DMA((block_f, ROWS)),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((Bp, Ep), jnp.float32),
        interpret=interpret,
    )(*scalars, *arrays)
    return out[:B, :E]


def _auto(interpret):
    """``interpret=None`` = compile on a TPU backend, interpret
    everywhere else."""
    return jax.default_backend() != "tpu" if interpret is None else interpret


@functools.partial(jax.jit,
                   static_argnames=("block_e", "block_f", "interpret"))
def pooled_lookup(
    table: jnp.ndarray,
    ids: jnp.ndarray,
    weights: jnp.ndarray | None = None,
    *,
    block_e: int = DEFAULT_BLOCK_E,
    block_f: int | None = None,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """sum_f table[ids[b, f]] * weights[b, f]  ->  (B, E) f32.

    ids: (B, F) int32, PAD = -1 (weight forced to 0).
    block_f: ids per bag fetched per grid step (None = 1); the grid is
    (bag blocks of 8, E-blocks, F / block_f).
    interpret: None = auto — compile for real on a TPU backend, interpret
    everywhere else (so TPU hosts get the compiled kernel without
    call-site edits).
    """
    return _pooled_call(ids, weights, table, None, None, block_e=block_e,
                        block_f=block_f or 1, interpret=_auto(interpret))


def _staged_kernel(src_ref, plane_ref, main_ref, tail_ref, out_ref, buf,
                   sems, *, block_e: int):
    out_ref[...] = plane_ref[...]
    cols = pl.ds(pl.multiple_of(pl.program_id(1) * block_e, block_e),
                 block_e)
    gather_rows_into(out_ref, src_ref, (main_ref, tail_ref), buf, sems, cols)


@functools.partial(jax.jit,
                   static_argnames=("block_e", "interpret"))
def staged_gather(
    plane_rows: jnp.ndarray,
    table: jnp.ndarray,
    src_rows: jnp.ndarray,
    *,
    block_e: int = DEFAULT_BLOCK_E,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """out[s] = table[src_rows[s]] if src_rows[s] >= 0 else plane_rows[s].

    The window-driven prefetch pull: ``src_rows`` (C,) names, per staging
    slot, the table row to pull (-1 = keep the slot's current row).  The
    grid walks the plane in blocks of 8 slots; ``src_rows`` streams in
    through scalar prefetch, each block copies through from the plane
    and only freshly staged slots DMA their row from the HBM-resident
    table.  Pull and merge into the cache plane are one kernel launch:
    no host round-trip, no scatter on the host side.

    plane_rows: (C, E) staging plane; table: (V, E); src_rows: (C,)
    int32.  Returns the merged (C, E) plane.
    """
    C, E = plane_rows.shape
    src = jnp.pad(jnp.asarray(src_rows).astype(jnp.int32), (0, (-C) % ROWS),
                  constant_values=-1)
    pln = _pad_lanes(plane_rows, block_e)
    main, tail = row_source(table.astype(plane_rows.dtype), block_e)
    Ep = pln.shape[1]

    out = pl.pallas_call(
        functools.partial(_staged_kernel, block_e=block_e),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(src.shape[0] // ROWS, Ep // block_e),
            in_specs=[
                pl.BlockSpec((ROWS, block_e), lambda s, e, src_: (s, e)),
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec((ROWS, block_e),
                                   lambda s, e, src_: (s, e)),
            scratch_shapes=[
                pltpu.VMEM((ROWS, _tile_rows(main.dtype), block_e),
                           main.dtype),
                pltpu.SemaphoreType.DMA((ROWS,)),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((C, Ep), plane_rows.dtype),
        interpret=_auto(interpret),
    )(src, pln, main, tail)
    return out[:, :E]


@functools.partial(jax.jit,
                   static_argnames=("block_e", "interpret"))
def pooled_lookup_staged(
    plane_rows: jnp.ndarray,
    table: jnp.ndarray,
    slots: jnp.ndarray,
    ids: jnp.ndarray,
    weights: jnp.ndarray | None = None,
    *,
    block_e: int = DEFAULT_BLOCK_E,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Pooled lookup that READS from the staging plane: per (bag, slot),
    ``row = plane_rows[slots[b, f]]`` when a live staging slot holds the
    id (``slots[b, f] >= 0``), else ``table[ids[b, f]]`` — the serving
    read path (repro.serve): a TTL-refreshed cache plane answers the
    lookup and only plane misses touch the canonical PS table.

    Same grid as :func:`pooled_lookup`; each lookup DMAs its row from
    exactly one source (the slot/id arrays ride scalar prefetch), so the
    plane-vs-table choice costs no second fetch and no host-side merge.

    plane_rows: (C, E); table: (V, E); slots: (B, F) int32 staging-slot
    index per lookup (-1 = canonical table; the caller projects the
    plane with ``repro.pipeline.prefetch.slot_map``); ids: (B, F) int32,
    PAD = -1 (weight forced to 0).  Returns (B, E) f32 pooled sums.
    """
    return _pooled_call(ids, weights, table, plane_rows, slots,
                        block_e=block_e, block_f=1,
                        interpret=_auto(interpret))


def _quant_kernel(ids_ref, w_ref, codes, codes_tail, meta_ref, out_ref,
                  buf, sems, *, F: int, block_e: int, B_grp: int, G: int,
                  E: int):
    b0 = pl.program_id(0) * ROWS
    e = pl.program_id(1)
    cols = pl.ds(pl.multiple_of(e * block_e, block_e), block_e)
    f = pl.program_id(2)

    @pl.when(f == 0)
    def _zero():
        out_ref[...] = jnp.zeros_like(out_ref)

    def dma(r):
        k = (b0 + r) * F + f
        idx = ids_ref[k]
        copies, off = row_dma((codes, codes_tail), jnp.maximum(idx, 0), cols,
                              buf.at[r], sems.at[r])
        return idx >= 0, copies, off, k

    @pl.loop(0, ROWS)
    def _issue(r):
        live, copies, _, _ = dma(r)
        _start(copies, live)

    # expand each row's per-group scale/zp over the E-block's columns (G
    # is static — unrolled); columns outside every group (the 128-lane
    # pad tail) dequantize to 0 and are sliced off by the wrapper
    col = e * block_e + jax.lax.broadcasted_iota(jnp.int32, (1, block_e), 1)

    @pl.loop(0, ROWS)
    def _land(r):
        live, copies, off, k = dma(r)
        _wait(copies, live)
        m = meta_ref[pl.ds(r, 1), :]
        sc = jnp.zeros((1, block_e), jnp.float32)
        zp = jnp.zeros((1, block_e), jnp.float32)
        for g in range(G):
            in_g = (col >= g * B_grp) & (col < min((g + 1) * B_grp, E))
            sc = jnp.where(in_g, m[:, g:g + 1], sc)
            zp = jnp.where(in_g, m[:, G + g:G + g + 1], zp)
        deq = buf[r, pl.ds(off, 1), :] * sc + zp
        out_ref[pl.ds(r, 1), :] += jnp.where(live, deq, 0.0) * w_ref[k]


@functools.partial(jax.jit,
                   static_argnames=("codec", "block_e", "interpret"))
def pooled_lookup_quant(
    codes: jnp.ndarray,
    scale: jnp.ndarray,
    zp: jnp.ndarray,
    ids: jnp.ndarray,
    weights: jnp.ndarray | None = None,
    *,
    codec,
    block_e: int = DEFAULT_BLOCK_E,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Pooled lookup over a QUANTIZED table: dequant fused into the
    per-row accumulate, so the f32 table never materializes.

    codes: (V, E) affine codes (float-valued ints, as
    :func:`repro.quant.codecs.quantize_rows` emits) or an fp16 cast;
    scale/zp: (V, G) per-group metadata; ids: (B, F) int32, PAD = -1.
    Each lookup DMAs one code row and accumulates ``(codes * scale + zp)
    * w`` in-register — bitwise the pooled sum of the dequantized
    (``fake_quant``-ed) table.  The metadata of the B x F looked-up rows
    is gathered up front (a few KB, not the (V, G) arrays: a DMA moves
    whole 128-lane tiles, which a G-wide row is not) and streams in per
    field as an (8 bags, scale | zp) VMEM block.
    """
    from ..quant.codecs import get_codec

    c = get_codec(codec)
    if c is None:
        raise ValueError("pooled_lookup_quant needs a codec")
    if c.kind == "fp16":
        return pooled_lookup(codes.astype(jnp.float32), ids, weights,
                             block_e=block_e, interpret=interpret)
    B, F = ids.shape
    V, E = codes.shape
    G = scale.shape[-1]
    B_grp = E if c.block is None else min(c.block, E)
    Bp = B + (-B) % ROWS
    ids_f, w_f = _flat_ids(ids, weights, Bp, F)
    rows = jnp.maximum(ids, 0).T                          # (F, B)
    meta = jnp.concatenate([scale[rows], zp[rows]], axis=-1)
    Mp = 2 * G + (-2 * G) % 128
    meta = jnp.pad(meta.astype(jnp.float32),
                   ((0, 0), (0, Bp - B), (0, Mp - 2 * G)))
    tbl = row_source(codes.astype(jnp.float32), block_e)
    Ep = tbl[0].shape[1]

    out = pl.pallas_call(
        functools.partial(_quant_kernel, F=F, block_e=block_e, B_grp=B_grp,
                          G=G, E=E),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(Bp // ROWS, Ep // block_e, F),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)] * 2 + [
                pl.BlockSpec((pl.Squeezed(), ROWS, Mp),
                             lambda b, e, f, *_: (f, b, 0))],
            out_specs=pl.BlockSpec((ROWS, block_e),
                                   lambda b, e, f, *_: (b, e)),
            scratch_shapes=[
                pltpu.VMEM((ROWS, _tile_rows(jnp.float32), block_e),
                           jnp.float32),
                pltpu.SemaphoreType.DMA((ROWS,)),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((Bp, Ep), jnp.float32),
        interpret=_auto(interpret),
    )(ids_f, w_f, *tbl, meta)
    return out[:B, :E]
