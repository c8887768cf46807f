"""Window-driven oracle prefetch: stage future-miss rows under training.

The lookahead window (:mod:`repro.pipeline.window`) already names every
id the next W batches will touch and when.  This module turns that
oracle into an asynchronous pull plane: while step t trains, the rows
the window says steps t+1..t+W will miss are moved from the PS tier
into a fixed-size *staging plane* on the trainer, so that when the miss
actually happens the row is already local — the miss still happens (the
cache-state accounting is unchanged), but its wire transfer was hidden
under a previous train step.  The split is reported per step as
``prefetch_hit`` (miss whose row was staged) vs ``demand_miss`` (miss
that pays its latency on the critical path).

Mechanics per step:

  1. :func:`prefetch_candidates` (host, numpy) ranks the window's ids by
     first use and stamps each with an absolute expiry step (its last
     use inside the window) — a fixed-size, PAD-padded candidate list.
  2. :func:`prefetch_step` (jit) refreshes expiries of already-staged
     ids, drops candidates that are cluster-resident or staged, and
     stages up to ``budget`` new rows into expired slots.  The row pull
     itself is :func:`repro.kernels.emb_lookup.staged_gather`: one
     Pallas launch that DMAs the selected table rows straight into the
     plane and carries every untouched slot through — no host
     round-trip, no host-side scatter.  With a ``codec`` the pulled rows
     go through ``fake_quant`` first, i.e. the plane holds exactly what
     the exchange wire format would deliver.
  3. :func:`staged_membership` projects the plane onto a (V,) bool mask
     which the cache-state update (``esd_state_update*(..., staged=)``)
     uses to split its miss counts.

The plane is a *transport* optimization: training always reads the
canonical table, so enabling prefetch at any window size W leaves the
loss trajectory bitwise unchanged — it moves bytes and accounting, not
values.  (Rowwise-adagrad makes the staged rows of ids that were not
re-trained in the meantime bitwise-fresh, which the tests pin; serving
lookups directly from the plane is recorded as an open item in the
roadmap.)
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from ..kernels.emb_lookup import staged_gather
from ..quant.codecs import fake_quant, get_codec

__all__ = ["PrefetchPlane", "prefetch_init", "prefetch_candidates",
           "prefetch_step", "staged_membership", "slot_map"]


@functools.partial(jax.tree_util.register_dataclass,
                   data_fields=("ids", "rows", "expiry"), meta_fields=())
@dataclasses.dataclass
class PrefetchPlane:
    """Fixed-capacity staging plane: slot s holds row ``rows[s]`` of id
    ``ids[s]`` (PAD = -1), reclaimable once the current step exceeds
    ``expiry[s]`` (the id's last scheduled use)."""

    ids: jnp.ndarray      # (C,) int32, -1 = empty slot
    rows: jnp.ndarray     # (C, E) f32 staged table rows
    expiry: jnp.ndarray   # (C,) int32 absolute last-use step, -1 = empty


def prefetch_init(slots: int, emb_dim: int) -> PrefetchPlane:
    """An empty plane with ``slots`` staging rows of width ``emb_dim``."""
    return PrefetchPlane(
        ids=jnp.full((slots,), -1, jnp.int32),
        rows=jnp.zeros((slots, emb_dim), jnp.float32),
        expiry=jnp.full((slots,), -1, jnp.int32),
    )


def prefetch_candidates(meta, step: int, max_cands: int,
                        part=None) -> tuple[np.ndarray, np.ndarray]:
    """Rank the window's ids into a fixed-size candidate list (host side).

    ``meta`` is the :class:`~repro.pipeline.window.WindowMeta` delivered
    with step ``step``'s batch, covering batches ``step+1 .. step+W``:
    an id whose ``first_use`` is f is next needed at absolute step
    ``step + 1 + f``.  Candidates are ordered by first use (most urgent
    first, so a budget cut drops the farthest-future rows) and stamped
    with ``expiry = step + 1 + last_use``.  Returns ``(ids, expiry)``
    int32 arrays of static length ``max_cands``, PAD = -1 (keeps the
    downstream jit shape-stable).  With ``part`` the ids are emitted in
    the PS-linearized space (what the cache planes index by).
    """
    ids = np.asarray(meta.uids, np.int64)
    if part is not None and ids.size:
        ids = np.asarray(part.to_linear(ids), np.int64)
    order = np.argsort(meta.first_use, kind="stable")
    ids = ids[order][:max_cands]
    expiry = (int(step) + 1 + np.asarray(meta.last_use,
                                         np.int64)[order][:max_cands])
    pad = max_cands - len(ids)
    out_ids = np.full(max_cands, -1, np.int32)
    out_exp = np.full(max_cands, -1, np.int32)
    out_ids[:len(ids)] = ids
    out_exp[:len(ids)] = expiry
    if pad < 0:  # unreachable (slices above), kept for clarity
        raise AssertionError
    return out_ids, out_exp


@functools.partial(jax.jit,
                   static_argnames=("budget", "codec", "use_pallas",
                                    "interpret"))
def prefetch_step(plane: PrefetchPlane, table: jnp.ndarray,
                  resident: jnp.ndarray, cand_ids: jnp.ndarray,
                  cand_expiry: jnp.ndarray, step,
                  *, budget: int, codec=None, use_pallas: bool = True,
                  interpret: bool | None = None):
    """One prefetch round: stage up to ``budget`` future-miss rows.

    plane: current staging plane; table: (V, E) canonical rows (PS
    tier); resident: (V,) bool cluster residency (a row some worker
    already caches is never a future miss worth staging); cand_ids /
    cand_expiry: (P,) from :func:`prefetch_candidates`; step: current
    absolute step (expiry clock).

    Policy, in order: (a) ids already staged only refresh their expiry;
    (b) resident ids are skipped; (c) the first ``min(budget, free
    slots)`` remaining candidates (candidates arrive urgency-sorted)
    are pulled into expired/empty slots via the fused
    :func:`staged_gather` kernel.  ``use_pallas=False`` pulls the same
    rows with an XLA gather instead — the path for a table sharded over
    several devices, which a Pallas kernel cannot be partitioned over.
    Returns ``(new_plane, n_pulled)``.
    """
    C = plane.ids.shape[0]
    P = cand_ids.shape[0]
    step = jnp.asarray(step, jnp.int32)
    V = table.shape[0]

    alive = (plane.ids >= 0) & (plane.expiry >= step)
    cvalid = cand_ids >= 0
    eq = (plane.ids[:, None] == cand_ids[None, :]) \
        & alive[:, None] & cvalid[None, :]                    # (C, P)
    # (a) refresh: a staged id that reappears in the window extends its
    # expiry to the newest last-use the oracle reports
    best = jnp.max(jnp.where(eq, cand_expiry[None, :], -1), axis=1)
    expiry0 = jnp.where(alive, jnp.maximum(plane.expiry, best), -1)
    ids0 = jnp.where(alive, plane.ids, -1)

    # (b)+(c) choose which candidates to stage
    staged_already = eq.any(axis=0)                           # (P,)
    res = resident[jnp.clip(cand_ids, 0, V - 1)] & cvalid
    want = cvalid & ~staged_already & ~res
    n_free = C - alive.sum()
    rank = jnp.cumsum(want.astype(jnp.int32)) - 1
    take = want & (rank < jnp.minimum(budget, n_free))

    # fixed-size selection: sel_cand[r] = candidate index taken at rank r
    scatter_to = jnp.where(take, rank, budget)
    sel_cand = jnp.full((budget,), -1, jnp.int32).at[scatter_to].set(
        jnp.arange(P, dtype=jnp.int32), mode="drop")
    sel_ok = sel_cand >= 0
    sel_cand_c = jnp.clip(sel_cand, 0, P - 1)
    sel_ids = jnp.where(sel_ok, cand_ids[sel_cand_c], -1)
    sel_exp = jnp.where(sel_ok, cand_expiry[sel_cand_c], -1)
    # rank r lands in the r-th dead slot (stable sort puts dead first;
    # take already guarantees r < n_free <= C)
    dead_first = jnp.argsort(alive, stable=True).astype(jnp.int32)
    if budget > C:
        dead_first = jnp.pad(dead_first, (0, budget - C),
                             constant_values=C)
    sel_slot = jnp.where(sel_ok, dead_first[:budget], C)      # C = drop

    new_ids = ids0.at[sel_slot].set(sel_ids, mode="drop")
    new_exp = expiry0.at[sel_slot].set(sel_exp, mode="drop")
    c = get_codec(codec)
    if c is None and use_pallas:
        src = jnp.full((C,), -1, jnp.int32).at[sel_slot].set(
            jnp.clip(sel_ids, 0, V - 1), mode="drop")
        new_rows = staged_gather(plane.rows, table, src,
                                 interpret=interpret)
    else:
        # wire-format path: the plane holds what the receiver would
        # reconstruct after the exchange codec (fake_quant = dequantized
        # codes), so staged-row freshness reflects the real transport
        pulled = table[jnp.clip(sel_ids, 0, V - 1)]
        if c is not None:
            pulled = fake_quant(pulled, c)
        new_rows = plane.rows.at[sel_slot].set(
            jnp.where(sel_ok[:, None], pulled, 0.0), mode="drop")
    n_pulled = take.sum().astype(jnp.int32)
    return PrefetchPlane(ids=new_ids, rows=new_rows,
                         expiry=new_exp), n_pulled


@functools.partial(jax.jit, static_argnames=("V",))
def slot_map(plane: PrefetchPlane, V: int, step) -> jnp.ndarray:
    """(V,) int32: the staging slot holding id x's live row at ``step``,
    -1 where no live slot exists.

    The projection the *serving* read path needs
    (:mod:`repro.serve.plane`): where :func:`staged_membership` only
    answers "is a fresh copy staged?", ``slot_map`` answers "which slot
    do I read it from?", so a lookup can gather plane rows directly and
    fall back to the canonical table per id.  If an id ever occupied two
    live slots the highest slot wins (deterministic; the prefetch and
    TTL admit paths never double-stage an id).
    """
    step = jnp.asarray(step, jnp.int32)
    alive = (plane.ids >= 0) & (plane.expiry >= step)
    idx = jnp.where(alive, plane.ids, V)
    C = plane.ids.shape[0]
    return jnp.full((V,), -1, jnp.int32).at[idx].max(
        jnp.arange(C, dtype=jnp.int32), mode="drop")


@functools.partial(jax.jit, static_argnames=("V",))
def staged_membership(plane: PrefetchPlane, V: int, step) -> jnp.ndarray:
    """(V,) bool: ids with a live staged row at ``step`` (feeds the
    ``staged=`` miss-split argument of the cache-state updates)."""
    step = jnp.asarray(step, jnp.int32)
    alive = (plane.ids >= 0) & (plane.expiry >= step)
    idx = jnp.where(alive, plane.ids, V)
    return jnp.zeros((V,), bool).at[idx].set(True, mode="drop")
