"""ESD as a first-class TPU feature: in-jit dispatch + all_to_all exchange.

Mapping of the paper's edge mechanism onto a TPU mesh (DESIGN.md §2):

  * "edge worker"  = one data-parallel shard (axis ``data``, and ``pod``);
  * "PS pulls/pushes over Ethernet" = gathers against the model-axis-
    sharded global embedding table;
  * heterogeneous 0.5/5 Gbps links = per-worker ``t_tran`` vector (for
    multi-pod meshes: intra-pod ICI vs inter-pod DCN, ~8x apart);
  * the dispatch itself: each shard solves its own m-sample assignment
    (paper §4.1 runs the dispatcher locally on each worker) and the
    samples move over one of two wire paths — the **padded** baseline
    (per-target capacity exactly m/n, one fixed-shape ``lax.all_to_all``)
    or the **ragged** executor (repro.exchange: pow2-budgeted send
    blocks + valid-count masks + receiver compaction), which with
    ``cap_slack > 0`` lets the assignment skew past m/n and strictly
    lowers the Alg.-1 objective under Zipf/heterogeneous-link skew.

Everything here is jit-compatible (runs inside the train step):
  * Alg. 1 cost matrix  — core.cost.cost_matrix_sparse_jnp by default
    (touched-ids gathers, O(k*F*n)); the dense cost_matrix_jnp and the
    Pallas kernels remain selectable via ``esd_dispatch``;
  * Heu                 — greedy scan with workload caps;
  * Opt                 — fixed-phase eps-scaled auction (while_loops);
  * HybridDis           — regret-sorted split between them (Alg. 2);
  * cache state machine — two engines:
      - ``esd_state_update``: dense (n, V) boolean-plane phases A/B/C with
        a full-vocab LRU top_k — the O(n*V)-per-step reference;
      - ``esd_state_update_sparse``: incremental update keyed on the
        (n, L) padded id lists each worker actually needs; scatter/gather
        touches only those ids, and the LRU cut runs over a bounded
        candidate set (previous survivors + this step's ids, <= capacity
        + 2L slots) instead of all V.  Equivalence-tested against the
        dense engine (identical counts and state), so the per-step cost is
        batch-bound: at V = 1e6 the dense top_k alone is ~O(n*V*log V)
        while the sparse cut is O(n*(capacity + L)).

Dense-vs-sparse crossover: like core.cost, the dense engine only wins for
toy vocabularies (V below a few thousand); everything paper-scale should
run the sparse engine.

Multi-PS (repro.ps): ids are translated once to the PS-linearized space
(``PsPartition.to_linear``: lin = shard * max_rows + local) and the sparse
engine runs unchanged on planes of width ``part.linear_size`` — segment
``[p*max_rows, (p+1)*max_rows)`` is the set of rows PS ``p`` tracks.
``esd_dispatch(part=...)`` costs misses/pushes at the owning shard's link
(t_tran becomes (n, n_ps)), ``esd_state_update_sparse(part=...)`` emits a
per-(worker, PS) op breakdown, and :func:`need_ids_local` projects the
padded need lists to per-PS local rows.  ``n_ps == 1`` is the identity
translation, so the single-PS path is bit-for-bit unchanged.
"""
from __future__ import annotations

import dataclasses
import warnings
from functools import partial
from typing import Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np

from .auction import _repair, _round_body
from .cost import (cost_matrix_jnp, cost_matrix_sparse_jnp,
                   cost_matrix_sparse_ps_jnp)

__all__ = ["EsdState", "esd_init", "esd_cost_matrix", "esd_decide",
           "esd_dispatch", "esd_reassign", "changed_samples_mask",
           "esd_state_update", "SparseEsdState", "esd_sparse_init",
           "esd_state_update_sparse", "need_ids_list", "need_ids_local",
           "heu_dispatch_jax", "auction_fixed", "hybrid_dispatch_jax",
           "dispatch_cap", "exchange_budget"]


# --------------------------------------------------------------------------
# jittable dispatch decision methods
# --------------------------------------------------------------------------
def _regret(C):
    if C.shape[1] == 1:
        return jnp.zeros((C.shape[0],), C.dtype)
    top2 = -jax.lax.top_k(-C, 2)[0]          # two smallest
    return top2[:, 1] - top2[:, 0]


def heu_dispatch_jax(C, cap: int, workload=None, order=None):
    """Greedy Heu (Alg. 2 L9-18) as a lax.scan.  C: (k, n) -> (k,)."""
    k, n = C.shape
    if workload is None:
        workload = jnp.zeros((n,), jnp.int32)
    if order is None:
        order = jnp.argsort(-_regret(C), stable=True)
    pref = jnp.argsort(C, axis=1, stable=True)           # (k, n)

    def body(wl, i):
        row = pref[i]
        free = wl[row] < cap
        # first preferred worker with spare capacity
        idx = jnp.argmax(free)
        j = row[idx]
        return wl.at[j].add(1), j

    _, js = jax.lax.scan(body, workload, order)
    return jnp.zeros((k,), jnp.int32).at[order].set(js)


def changed_samples_mask(samples, state_a, state_b):
    """(m,) bool — samples holding >= 1 id whose Alg.-1 state column
    (``latest`` or ``dirty``) differs between two (Sparse)EsdStates.

    The jit twin of ``repro.pipeline.double_buffer.changed_ids``
    restricted to one batch: exactly the samples whose stale decide-time
    cost row can differ from the committed-state truth, i.e. the only
    rows :func:`esd_reassign` needs to re-place.  PAD (-1) ids never
    flag a sample.
    """
    V = state_a.latest.shape[1]
    valid = samples >= 0
    g = jnp.clip(samples, 0, V - 1)
    diff = ((state_a.latest[:, g] != state_b.latest[:, g])
            | (state_a.dirty[:, g] != state_b.dirty[:, g])).any(axis=0)
    return (diff & valid).any(axis=1)


def esd_reassign(C, assign, flagged, cap: int):
    """Repair a stale assignment against a fresh cost matrix.

    Keeps every unflagged sample on its stale worker (its cost row is
    bitwise what the decide-time state produced, so the stale choice is
    still exact) and greedily re-places the flagged rows in regret order
    on their cheapest worker with spare capacity — the same capped scan
    as :func:`heu_dispatch_jax`, seeded with the unflagged workload.

    C: (k, n) committed-state cost matrix; ``flagged`` from
    :func:`changed_samples_mask`.  Feasible whenever the stale assignment
    was (``cap * n >= k``).  Returns ``(assign, n_reassigned)``.
    """
    k, n = C.shape
    assign = assign.astype(jnp.int32)
    wl = jnp.zeros((n,), jnp.int32).at[assign].add((~flagged).astype(jnp.int32))
    # flagged rows first, by regret (the scan must see them before the
    # pass-through rows so capacity fills in regret order)
    order = jnp.argsort(-jnp.where(flagged, _regret(C), -jnp.inf),
                        stable=True)
    pref = jnp.argsort(C, axis=1, stable=True)

    def body(wl, i):
        row = pref[i]
        j_new = row[jnp.argmax(wl[row] < cap)]
        j = jnp.where(flagged[i], j_new, assign[i])
        return wl.at[j_new].add(flagged[i].astype(jnp.int32)), j

    _, js = jax.lax.scan(body, wl, order)
    return (jnp.zeros((k,), jnp.int32).at[order].set(js),
            flagged.sum().astype(jnp.int32))


@partial(jax.jit, static_argnames=("capacity", "n_phases", "rounds_per_phase"))
def auction_fixed(C, capacity: int, n_phases: int = 7,
                  rounds_per_phase: int = 2000):
    """Fully-traced eps-scaled auction (fixed phase schedule) — the in-step
    Opt.  Returns (k,) assignment (-1 never remains for feasible inputs
    given enough rounds; callers fall back greedily on any stragglers)."""
    k, n = C.shape
    C = C.astype(jnp.float32)
    span = jnp.maximum(jnp.max(C) - jnp.min(C), 1e-6)
    state = (
        jnp.full((k,), -1, jnp.int32),
        jnp.zeros((n, capacity), jnp.float32),
        jnp.full((n, capacity), -1, jnp.int32),
    )

    def phase(p, state):
        # clamp: extra terminal phases rerun repair + rebid at eps_final
        # until it fixes (repair reprices freed "dead capital" to zero,
        # so one pass after a tie war can still leave movable rows)
        e_pow = jnp.minimum(p, n_phases - 1).astype(jnp.float32)
        eps = span / 2.0 / (6.0 ** e_pow)
        state = jax.lax.cond(p > 0, lambda s: _repair(C, eps, s),
                             lambda s: s, state)

        def cond(carry):
            st, it = carry
            return (st[0] < 0).any() & (it < rounds_per_phase)

        def body(carry):
            st, it = carry
            return _round_body(C, eps, st), it + 1

        state, _ = jax.lax.while_loop(cond, body, (state, 0))
        return state

    state = jax.lax.fori_loop(0, n_phases + 2,
                              lambda p, s: phase(p, s), state)
    return state[0]


def hybrid_dispatch_jax(C, m: int, alpha: float, cap: Optional[int] = None):
    """Alg. 2 in-jit: top floor(k*alpha) regret rows -> auction, rest ->
    greedy.  Per-worker capacity defaults to the hard m/n split; pass
    ``cap > m/n`` (esd_dispatch's ``cap_slack``) to let the assignment
    skew — feasible because the ragged exchange no longer needs equal
    groups, and skew strictly lowers the Alg.-1 objective."""
    k, n = C.shape
    if n == 1:
        return jnp.zeros((k,), jnp.int32)
    if cap is None:
        cap = m // n if m >= n else 1
    if cap * n < k:
        raise ValueError(f"infeasible: cap {cap} * n {n} < k {k}")
    if alpha <= 0.0:
        return heu_dispatch_jax(C, cap)
    opt_cap = int(np.floor(cap * alpha)) if alpha < 1.0 else cap
    opt_rows = min(int(np.floor(k * alpha)), opt_cap * n)
    if opt_rows == 0:
        return heu_dispatch_jax(C, cap)
    order = jnp.argsort(-_regret(C), stable=True)
    opt_idx, heu_idx = order[:opt_rows], order[opt_rows:]
    assign = jnp.full((k,), -1, jnp.int32)
    a_opt = auction_fixed(C[opt_idx], opt_cap)
    # stragglers (tie wars the terminal repair phases didn't settle):
    # place each on its cheapest worker WITH SPARE CAPACITY — dumping
    # them all on one argmin-loaded worker can exceed ``cap``, and the
    # ragged wire drops every over-budget row (launch.steps raises on
    # the overflow counter).  opt_rows <= opt_cap * n guarantees a free
    # slot exists for every straggler.
    placed = a_opt >= 0
    wl0 = jnp.zeros((n,), jnp.int32).at[
        jnp.where(placed, a_opt, 0)].add(placed.astype(jnp.int32))
    pref_opt = jnp.argsort(C[opt_idx], axis=1, stable=True)

    def _place(wl, i):
        row = pref_opt[i]
        j_new = row[jnp.argmax(wl[row] < opt_cap)]
        j = jnp.where(placed[i], a_opt[i], j_new)
        return wl.at[j_new].add(jnp.int32(~placed[i])), j

    _, a_opt = jax.lax.scan(_place, wl0,
                            jnp.arange(opt_rows, dtype=jnp.int32))
    assign = assign.at[opt_idx].set(a_opt)
    if opt_rows < k:
        workload = jnp.zeros((n,), jnp.int32).at[a_opt].add(1)
        a_heu = heu_dispatch_jax(C[heu_idx], cap, workload=workload)
        assign = assign.at[heu_idx].set(a_heu)
    return assign


# --------------------------------------------------------------------------
# replicated cache state + accounting (vectorized core.cache phases)
# --------------------------------------------------------------------------
@partial(jax.tree_util.register_dataclass,
         data_fields=("latest", "dirty", "last_access", "step"),
         meta_fields=())
@dataclasses.dataclass
class EsdState:
    latest: jnp.ndarray        # (n, V) bool — latest version resident
    dirty: jnp.ndarray         # (n, V) bool — unsynced local gradient
    last_access: jnp.ndarray   # (n, V) int32
    step: jnp.ndarray          # () int32


def esd_init(n_workers: int, vocab: int) -> EsdState:
    # latest/dirty must be distinct buffers (donation rejects aliases)
    return EsdState(jnp.zeros((n_workers, vocab), bool),
                    jnp.zeros((n_workers, vocab), bool),
                    jnp.zeros((n_workers, vocab), jnp.int32),
                    jnp.zeros((), jnp.int32))


def esd_state_update(state: EsdState, need: jnp.ndarray,
                     capacity: Optional[int] = None, staged=None):
    """One BSP iteration of the cache protocol on the replicated state.

    need: (n, V) bool — ids each worker trains this iteration (post-
    dispatch).  Returns (new_state, counts dict with per-worker miss_pull /
    update_push / evict_push).

    ``staged``: optional (V,) bool membership of the prefetch staging
    plane (``repro.pipeline.prefetch``).  A miss on a staged id is served
    locally instead of pulling the PS at need time, so the counts gain
    the ``prefetch_hit`` / ``demand_miss`` split of ``miss_pull``; the
    state transition itself is unchanged (the pull happened earlier and
    is priced as prefetch bytes).  ``staged=None`` is the bitwise path.
    """
    latest, dirty = state.latest, state.dirty
    n, V = need.shape
    step = state.step + 1

    # Phase A: on-demand update push
    need_any = need.any(axis=0)
    sole = need & (need.sum(axis=0) == 1)[None, :]
    need_other = need_any[None, :] & ~sole
    pushers = dirty & need_other
    update_push = pushers.sum(axis=1)
    pushed = pushers.any(axis=0)
    multi = pushers.sum(axis=0) > 1
    latest = latest & ~(pushed[None, :] & ~pushers) & ~multi[None, :]
    dirty = dirty & ~pushers

    # Phase B: miss pull
    miss = need & ~latest
    miss_pull = miss.sum(axis=1)
    latest = latest | need

    # Phase C: train
    dirty = dirty | need
    trained = need.any(axis=0)
    latest = latest & ~(trained[None, :] & ~need)
    last_access = jnp.where(need, step, state.last_access)

    # optional LRU capacity: evict all but the `capacity` most recent
    evict_push = jnp.zeros((n,), jnp.int32)
    if capacity is not None and capacity < V:
        if capacity == 0:
            # nothing survives past its own iteration (the V-capacity
            # index below would clamp to V-1 and wrongly spare one id)
            keep = need
        else:
            # strict LRU cut on the (last_access, id) pair: tie-break
            # equal access times by id so the keep set is exactly
            # `capacity` (+ pinned current ids).  A two-key lexicographic
            # sort avoids the int32 overflow a packed last_access*V + id
            # key would hit at paper scale (x64 is disabled, so int64
            # silently truncates).
            ids_row = jnp.broadcast_to(jnp.arange(V, dtype=jnp.int32), (n, V))
            sla, sid = jax.lax.sort((last_access, ids_row), dimension=1,
                                    num_keys=2)
            kth_la = sla[:, V - capacity][:, None]
            kth_id = sid[:, V - capacity][:, None]
            keep = (last_access > kth_la) | ((last_access == kth_la)
                                             & (ids_row >= kth_id))
            keep = keep | need            # pinned
        evicted = latest & ~keep
        evict_push = (evicted & dirty).sum(axis=1)
        dirty = dirty & keep
        latest = latest & keep

    new = EsdState(latest, dirty, last_access, step)
    counts = {"miss_pull": miss_pull, "update_push": update_push,
              "evict_push": evict_push}
    if staged is not None:
        pre = (miss & staged[None, :]).sum(axis=1)
        counts["prefetch_hit"] = pre
        counts["demand_miss"] = miss_pull - pre
    return new, counts


# --------------------------------------------------------------------------
# sparse (touched-ids) cache state + accounting
# --------------------------------------------------------------------------
@partial(jax.tree_util.register_dataclass,
         data_fields=("latest", "dirty", "last_access", "slots", "step"),
         meta_fields=())
@dataclasses.dataclass
class SparseEsdState:
    """Replicated cache state for the incremental engine.

    latest/dirty/last_access are the same (n, V) planes as
    :class:`EsdState` — kept as O(1)-lookup storage, but only ever
    scatter-updated at touched ids.  ``slots`` (n, S) holds the ids that
    survived the last LRU cut (PAD = -1); it is the bounded candidate set
    the next cut ranks, so no step ever sorts all V keys.
    """
    latest: jnp.ndarray        # (n, V) bool
    dirty: jnp.ndarray         # (n, V) bool
    last_access: jnp.ndarray   # (n, V) int32
    slots: jnp.ndarray         # (n, S) int32, PAD = -1
    step: jnp.ndarray          # () int32


def esd_sparse_init(n_workers: int, vocab: int,
                    capacity: Optional[Union[int, Sequence[int]]] = None,
                    max_ids: int = 0) -> SparseEsdState:
    """``max_ids`` = L, the per-worker padded id-list width the state will
    be stepped with (needed to size the slot buffer: S = capacity + L).

    ``capacity`` may be a per-PS sequence (one worker-cache budget per
    parameter server, see :func:`esd_state_update_sparse`); the slot
    buffer then holds one (cap_p + L)-wide segment per shard.
    """
    if capacity is not None and np.ndim(capacity) > 0:
        S = int(sum(int(c) + max_ids for c in capacity))
    else:
        S = 0 if capacity is None or capacity >= vocab else capacity + max_ids
    return SparseEsdState(jnp.zeros((n_workers, vocab), bool),
                          jnp.zeros((n_workers, vocab), bool),
                          jnp.zeros((n_workers, vocab), jnp.int32),
                          jnp.full((n_workers, S), -1, jnp.int32),
                          jnp.zeros((), jnp.int32))


def esd_state_update_sparse(state: SparseEsdState, need_ids: jnp.ndarray,
                            capacity: Optional[Union[int, Sequence[int]]] = None,
                            part=None, staged=None):
    """Incremental BSP iteration: same protocol and counts as
    :func:`esd_state_update`, driven by touched ids only.

    need_ids: (n, L) int32 — the ids each worker trains this iteration,
    **unique within each row**, PAD = -1 (see :func:`need_ids_list`).
    Returns (new_state, counts).

    With ``part`` (a static :class:`repro.ps.PsPartition`; ids and planes
    in its PS-linearized space) the counts dict additionally carries the
    per-(worker, PS) breakdown ``{miss_pull,update_push,evict_push}_ps``
    of shape (n, n_ps), so the caller can charge per-shard link costs.
    The state transition itself is unchanged.

    ``capacity`` may then also be a length-``n_ps`` sequence of per-PS
    worker-cache budgets: each worker keeps at most ``capacity[p]`` ids
    owned by shard ``p`` and the LRU cut runs independently per shard
    (init the state with the same sequence so the slot buffer carries
    one segment per shard).  A plain int is the unchanged (bitwise)
    single-budget path.

    ``staged``: optional (V,) bool prefetch-plane membership (linear id
    space) — adds the ``prefetch_hit`` / ``demand_miss`` split of
    ``miss_pull`` to the counts without touching the state transition;
    see :func:`esd_state_update`.
    """
    n, L = need_ids.shape
    V = state.latest.shape[1]
    if part is not None and V != part.linear_size:
        raise ValueError(
            f"state plane width {V} != part.linear_size {part.linear_size}: "
            "multi-PS state runs on the PS-linearized id space")
    capacity_ps = None
    if capacity is not None and np.ndim(capacity) > 0:
        if part is None:
            raise ValueError("per-PS capacity budgets need part=")
        if len(capacity) != part.n_ps:
            raise ValueError(f"capacity_ps has {len(capacity)} entries for "
                             f"n_ps = {part.n_ps}")
        capacity_ps = tuple(int(c) for c in capacity)
    step = state.step + 1
    valid = need_ids >= 0

    with jax.named_scope("universe"):
        # touched-id universe: sorted unique over all workers, pad sentinel V
        flat = jnp.where(valid, need_ids, V).reshape(-1)
        uids = jnp.unique(flat, size=n * L, fill_value=V)      # (U,) sorted
        uvalid = uids < V
        g = jnp.minimum(uids, V - 1)                   # safe gather col
        rows = jnp.arange(n)[:, None]

        # need membership on the compact universe
        pos = jnp.searchsorted(uids, jnp.where(valid, need_ids, V))
        needU = (jnp.zeros((n, uids.shape[0]), jnp.int32)
                 .at[rows, pos].add(valid.astype(jnp.int32), mode="drop")) > 0

    with jax.named_scope("phases"):
        latU = state.latest[:, g] & uvalid[None, :]
        dirU = state.dirty[:, g] & uvalid[None, :]
        lastU = state.last_access[:, g]

        # Phase A: on-demand update push
        need_anyU = needU.any(axis=0)
        sole = needU & (needU.sum(axis=0) == 1)[None, :]
        need_other = need_anyU[None, :] & ~sole
        pushers = dirU & need_other
        update_push = pushers.sum(axis=1)
        pushed = pushers.any(axis=0)
        multi = pushers.sum(axis=0) > 1
        latU = latU & ~(pushed[None, :] & ~pushers) & ~multi[None, :]
        dirU = dirU & ~pushers

        # Phase B: miss pull
        miss = needU & ~latU
        miss_pull = miss.sum(axis=1)
        latU = latU | needU

        # Phase C: train
        dirU = dirU | needU
        latU = latU & ~(need_anyU[None, :] & ~needU)
        lastU = jnp.where(needU, step, lastU)

        # scatter the touched columns back; pad columns are routed out of
        # bounds and dropped so they can never alias a real column's write
        gs = jnp.where(uvalid, uids, V)
        latest = state.latest.at[:, gs].set(latU, mode="drop")
        dirty = state.dirty.at[:, gs].set(dirU, mode="drop")
        last_access = state.last_access.at[:, gs].set(lastU, mode="drop")

    with jax.named_scope("capacity_cut"):
        # optional LRU capacity: strict cut over the bounded candidate set
        # (previous survivors + this step's ids), identical to the dense
        # full-vocab top_k because every id outside the candidate set has a
        # strictly smaller recency key than every id inside it.
        #
        # One ascending sort of the candidate keys does all the work: pinned
        # ids (just stamped last_access = step) hold the globally largest
        # keys, so the kept set is a contiguous suffix of the sorted keys and
        # the evicted candidates (at most 2L of them) sit in a contiguous
        # zone right below the top-capacity block — no argsort, no
        # candidate-wide scatters.
        evict_push = jnp.zeros((n,), jnp.int32)
        evict_push_ps = (jnp.zeros((n, part.n_ps), jnp.int32)
                         if part is not None else None)
        slots = state.slots
        if capacity_ps is not None:
            # per-PS budgets: the identical strict cut, run once per shard
            # over that shard's candidates (its slot segment + this step's
            # ids homed there), each against its own capacity[p]
            offs = np.cumsum([0] + [c + L for c in capacity_ps])
            if slots.shape[1] < offs[-1]:
                raise ValueError(
                    f"slot buffer {slots.shape[1]} < sum(cap_p + L) = "
                    f"{offs[-1]}; "
                    "init the state with esd_sparse_init(..., capacity_ps, "
                    "max_ids=L)")
            shard_need = part.shard_of_linear(jnp.where(valid, need_ids, 0))
            new_segs, ev_counts = [], []
            for p, cap_p in enumerate(capacity_ps):
                valid_p = valid & (shard_need == p)
                need_p = jnp.where(valid_p, need_ids, -1)
                slots_p = state.slots[:, offs[p]:offs[p] + cap_p + L]
                # segment p holds only ids homed at shard p, so the stamp
                # test (see the single-budget branch) is membership of need_p
                la_s = last_access[rows, jnp.clip(slots_p, 0, V - 1)]
                slot_cand = jnp.where((la_s == step) & (slots_p >= 0), -1,
                                      slots_p)
                cand = jnp.concatenate([need_p, slot_cand], axis=1)
                la_c = jnp.concatenate(
                    [jnp.where(valid_p, step, -1),
                     jnp.where(slot_cand >= 0, la_s, -1)], axis=1)
                sla, sid = jax.lax.sort((la_c, cand), dimension=1, num_keys=2)
                T_p = cand.shape[1]                      # = cap_p + 2L
                zone = slice(T_p - cap_p - 2 * L, T_p - cap_p)
                ev = (sla[:, zone] >= 0) & (sla[:, zone] < step)
                ev_ids = jnp.where(ev, sid[:, zone], V)
                egc = jnp.minimum(ev_ids, V - 1)
                lat_e = latest[rows, egc] & ev
                dr_e = dirty[rows, egc] & ev
                ev_counts.append((lat_e & dr_e).sum(axis=1).astype(jnp.int32))
                latest = latest.at[rows, ev_ids].set(False, mode="drop")
                dirty = dirty.at[rows, ev_ids].set(False, mode="drop")
                S_p = cap_p + L
                top_la, top_id = sla[:, T_p - S_p:], sid[:, T_p - S_p:]
                keepm = (top_la >= 0) & (
                    (jnp.arange(S_p) >= S_p - cap_p)[None, :]
                    | (top_la == step))
                new_segs.append(jnp.where(keepm, top_id, -1))
            evict_push = sum(ev_counts)
            # part is never None here
            evict_push_ps = jnp.stack(ev_counts, axis=1)
            slots = jnp.concatenate(new_segs, axis=1)
            if slots.shape[1] < state.slots.shape[1]:
                slots = jnp.concatenate(
                    [slots, jnp.full((n, state.slots.shape[1]
                                      - slots.shape[1]),
                                     -1, jnp.int32)], axis=1)
        elif capacity is not None and capacity < V:
            if slots.shape[1] < capacity + L:
                raise ValueError(
                    f"slot buffer {slots.shape[1]} < capacity+L = "
                    f"{capacity + L}; init the state with "
                    "esd_sparse_init(..., capacity, max_ids=L)")
            S = slots.shape[1]
            # candidates: this step's ids (pinned) + previous survivors with
            # duplicates of this step's ids masked out.  Slot s is in row j's
            # need list iff Phase C stamped last_access[j, s] = step: every
            # other stamp is at most step - 1, so no search is needed.
            la_s = last_access[rows, jnp.clip(slots, 0, V - 1)]      # (n, S)
            slot_cand = jnp.where((la_s == step) & (slots >= 0), -1, slots)
            cand = jnp.concatenate(
                [jnp.where(valid, need_ids, -1), slot_cand], axis=1)   # (n, T)
            # two-key lexicographic sort on (last_access, id): same strict
            # order as the dense engine's cut without the int32 overflow a
            # packed la*V + id key would hit at paper scale (x64 disabled).
            # Invalid candidates get la = -1 so they sort below every valid
            # one (valid la >= 0); this step's ids were just stamped step.
            la_c = jnp.concatenate(
                [jnp.where(valid, step, -1),
                 jnp.where(slot_cand >= 0, la_s, -1)], axis=1)
            sla, sid = jax.lax.sort((la_c, cand), dimension=1, num_keys=2)
            T = cand.shape[1]

            # evicted zone: valid, non-pinned entries directly below the
            # top-capacity block (never more than 2L evictions per step)
            zone = slice(T - capacity - 2 * L, T - capacity)
            # pinned: la == step; V: drop
            ev = (sla[:, zone] >= 0) & (sla[:, zone] < step)
            ev_ids = jnp.where(ev, sid[:, zone], V)
            egc = jnp.minimum(ev_ids, V - 1)
            lat_e = latest[rows, egc] & ev
            dr_e = dirty[rows, egc] & ev
            evict_push = (lat_e & dr_e).sum(axis=1).astype(jnp.int32)
            if part is not None:
                # non-evicted slots (shard of the sentinel V is out of range
                # for n_ps > 1) are already masked out by lat_e/dr_e
                shard_e = part.shard_of_linear(ev_ids)
                evict_push_ps = ((lat_e & dr_e)[:, :, None]
                                 & (shard_e[:, :, None]
                                    == jnp.arange(part.n_ps)[None, None, :])
                                 ).sum(axis=1).astype(jnp.int32)
            latest = latest.at[rows, ev_ids].set(False, mode="drop")
            dirty = dirty.at[rows, ev_ids].set(False, mode="drop")

            # new slots: the kept suffix = top-capacity block plus any pinned
            # spill right below it (only when a batch exceeds capacity)
            top_la, top_id = sla[:, T - S:], sid[:, T - S:]            # (n, S)
            keepm = (top_la >= 0) & ((jnp.arange(S) >= S - capacity)[None, :]
                                     | (top_la == step))
            slots = jnp.where(keepm, top_id, -1)

    new = SparseEsdState(latest, dirty, last_access, slots, step)
    counts = {"miss_pull": miss_pull, "update_push": update_push,
              "evict_push": evict_push}
    if staged is not None:
        stagedU = staged[g] & uvalid
        pre = (miss & stagedU[None, :]).sum(axis=1)
        counts["prefetch_hit"] = pre
        counts["demand_miss"] = miss_pull - pre
    if part is not None:
        # per-shard breakdown on the touched universe; sentinel columns
        # never hold a set miss/pusher bit, so their shard is irrelevant
        onehot = part.shard_of_linear(uids)[:, None] == jnp.arange(part.n_ps)
        onehot = onehot.astype(jnp.int32)                          # (U, p)
        counts["miss_pull_ps"] = miss.astype(jnp.int32) @ onehot
        counts["update_push_ps"] = pushers.astype(jnp.int32) @ onehot
        counts["evict_push_ps"] = evict_push_ps
    return new, counts


# --------------------------------------------------------------------------
# the shard_map dispatch + exchange
# --------------------------------------------------------------------------
_pallas_ps_warned = False


def _warn_pallas_ps_fallback():
    """One-time notice that multi-PS Alg. 1 degrades to the jnp path."""
    global _pallas_ps_warned
    if not _pallas_ps_warned:
        warnings.warn(
            "esd_dispatch(use_pallas=True) with n_ps > 1: the ps-aware "
            "Alg. 1 has no Pallas variant yet — falling back to "
            "cost_matrix_sparse_ps_jnp (see ROADMAP multi-PS item)",
            RuntimeWarning, stacklevel=3)
        _pallas_ps_warned = True


def dispatch_cap(m: int, n: int, cap_slack: float = 0.0) -> int:
    """Per-(shard, worker) dispatch capacity: the hard m/n split relaxed
    by ``cap_slack`` (fraction of m/n a worker may exceed it by)."""
    base = m // n if m >= n else 1
    if cap_slack <= 0.0:
        return base
    return min(m, int(np.ceil(base * (1.0 + cap_slack))))


def exchange_budget(cap: int, m: int) -> int:
    """Static per-link send-block rows for the ragged executor: the
    capacity bucketed up to a power of two (<= m), so sweeping cap_slack
    recompiles once per bucket instead of once per cap value."""
    return min(m, 1 << max(cap - 1, 0).bit_length())


def esd_cost_matrix(samples, state, t_tran, use_pallas: bool = False,
                    sparse_cost: bool = True, part=None, col_bias=None):
    """This shard's (m, n) Alg. 1 cost matrix under ``state`` — the
    branch selection shared by :func:`esd_decide` and the pipeline's
    commit-time re-score (``repro.pipeline``: score a *stale* decision
    against the state it actually committed on).

    ``col_bias`` (elastic clusters, ``repro.elastic.cost_column_bias``):
    an (n,) per-worker additive term — straggler excess compute, or the
    finite dead-worker penalty.  Passed as an *array* so churn changes
    values, never shapes (no recompile); ``None`` and an all-zero bias
    are bitwise-identical (costs are >= 0, so ``C + 0.0`` is identity).
    """
    if part is not None and part.n_ps > 1:
        if use_pallas:
            _warn_pallas_ps_fallback()
        C = cost_matrix_sparse_ps_jnp(samples, state.latest, state.dirty,
                                      t_tran, part, linear=True)
    elif use_pallas:
        from ..kernels.ops import cost_matrix_pallas, cost_matrix_pallas_sparse
        kern = cost_matrix_pallas_sparse if sparse_cost else cost_matrix_pallas
        C = kern(samples, state.latest, state.dirty, t_tran)
    else:
        fn = cost_matrix_sparse_jnp if sparse_cost else cost_matrix_jnp
        C = fn(samples, state.latest, state.dirty, t_tran)
    if col_bias is not None:
        C = C + col_bias[None, :].astype(C.dtype)
    return C


def esd_decide(samples, state, t_tran, alpha: float,
               axis_name: str = "data", use_pallas: bool = False,
               sparse_cost: bool = True, part=None,
               cap_slack: float = 0.0, with_cost: bool = False,
               col_bias=None, cap: int | None = None):
    """The decision half of :func:`esd_dispatch`: Alg. 1 cost matrix +
    hybrid assignment, no wire movement.

    Factored out so the pipelined executor (``repro.pipeline.runner``)
    can run the decision for step t+1 as its own jitted stage while step
    t trains.  Returns ``assign`` (m,) int32, or ``(assign, alg1)`` with
    ``with_cost`` — ``alg1`` is this shard's Alg.-1 objective of the
    chosen assignment (sum of C[i, assign[i]]), the number a stale
    decision's commit-time correction re-scores.

    Elastic clusters: ``col_bias`` biases the cost columns (see
    :func:`esd_cost_matrix`) and ``cap`` overrides the default
    ``dispatch_cap(m, n, cap_slack)`` — a churn-tolerant driver must
    raise the static capacity so the survivors of the worst planned
    simultaneous loss can absorb every sample without a reshape.
    """
    m, F = samples.shape
    # constant-folds to the static mesh axis size at trace time
    n = jax.lax.psum(1, axis_name)
    C = esd_cost_matrix(samples, state, t_tran, use_pallas=use_pallas,
                        sparse_cost=sparse_cost, part=part,
                        col_bias=col_bias)
    if cap is None:
        cap = dispatch_cap(m, n, cap_slack)
    assign = hybrid_dispatch_jax(C, m, alpha, cap=cap)
    if with_cost:
        alg1 = jnp.take_along_axis(C, assign[:, None], axis=1)[:, 0].sum()
        return assign, alg1
    return assign


def esd_dispatch(samples, state, t_tran, alpha: float,
                 axis_name: str = "data", use_pallas: bool = False,
                 sparse_cost: bool = True, part=None,
                 cap_slack: float = 0.0, exchange: str = "padded",
                 col_bias=None):
    """Inside shard_map over ``axis_name``: dispatch this shard's samples.

    samples: (m, F) local ids.  Returns (exchanged_samples, assign).

    ``exchange`` selects the wire path:
      * ``"padded"`` — every shard sends exactly m/n samples to each
        worker: one fixed-shape all_to_all, the bitwise baseline.
        Requires ``cap_slack == 0`` (equal groups).
      * ``"ragged"`` — the repro.exchange executor: per-destination send
        blocks of a static pow2 budget with valid-count masks, receiver
        compaction.  With ``cap_slack == 0`` the budget is exactly m/n
        and the result is bitwise-equal to the padded path (n = 1
        trivially so); with ``cap_slack > 0`` the assignment may give a
        worker up to ``dispatch_cap(m, n, cap_slack)`` samples per
        shard — strictly lowering the Alg.-1 objective under skew — and
        the exchanged batch comes back as (n * budget, F) with the valid
        rows compacted to the front and PAD (-1) rows after.

    ``sparse_cost`` selects the touched-ids Alg. 1 path (O(m*F*n), the
    default) over the dense (V, n)-table path; both are equivalence-tested.
    With ``use_pallas`` the corresponding Pallas kernel variant computes
    the cost matrix and the ragged pack runs the one-pass Pallas kernel.

    Multi-PS: pass ``part`` (a static :class:`repro.ps.PsPartition` with
    ``n_ps > 1``) plus a per-(worker, PS) ``t_tran`` of shape (n, n_ps);
    samples and the state planes must then be in the PS-linearized space,
    and a miss/push on an id is costed at the owning shard's link.
    ``use_pallas`` degrades to the jnp ps cost matrix (no ps Pallas
    kernel yet) with a one-time RuntimeWarning.
    """
    m, F = samples.shape
    if exchange not in ("padded", "ragged"):
        raise ValueError(f"unknown exchange mode {exchange!r}")
    if cap_slack > 0.0 and exchange != "ragged":
        raise ValueError("cap_slack > 0 needs exchange='ragged' (the padded "
                         "all_to_all requires equal m/n groups)")
    # constant-folds to the static mesh axis size at trace time
    # (jax.lax.axis_size is not available on this jax version)
    n = jax.lax.psum(1, axis_name)
    assign = esd_decide(samples, state, t_tran, alpha, axis_name=axis_name,
                        use_pallas=use_pallas, sparse_cost=sparse_cost,
                        part=part, cap_slack=cap_slack, col_bias=col_bias)
    cap = dispatch_cap(m, n, cap_slack)
    if exchange == "ragged":
        from ..exchange.ragged import ragged_exchange
        budget = cap if cap_slack <= 0.0 else exchange_budget(cap, m)
        out_rows = m if cap_slack <= 0.0 else n * budget
        out, _, _, _ = ragged_exchange(samples, assign, axis_name, budget,
                                       out_rows=out_rows,
                                       use_pallas=use_pallas)
        return out, assign
    order = jnp.argsort(assign, stable=True)             # groups of m/n
    routed = samples[order].reshape(n, m // n, F)
    exchanged = jax.lax.all_to_all(routed, axis_name, 0, 0, tiled=False)
    return exchanged.reshape(m, F), assign


def need_matrix(local_samples, axis_name: str, vocab: int):
    """(n, V) bool need matrix from each shard's post-exchange samples."""
    idx = jnp.where(local_samples >= 0, local_samples, vocab)  # PAD -> OOB
    mine = jnp.zeros((vocab,), bool).at[idx.reshape(-1)].set(True, mode="drop")
    return jax.lax.all_gather(mine, axis_name)           # (n, V)


def need_ids_list(local_samples, axis_name: str):
    """(n, L) padded unique-id lists from each shard's post-exchange
    samples — the sparse twin of :func:`need_matrix` (L = m*F, PAD = -1).
    Rows are unique and sorted, as :func:`esd_state_update_sparse` requires."""
    imax = jnp.iinfo(jnp.int32).max
    flat = local_samples.reshape(-1)
    u = jnp.unique(jnp.where(flat >= 0, flat, imax),
                   size=flat.shape[0], fill_value=imax)
    mine = jnp.where(u == imax, -1, u).astype(jnp.int32)
    return jax.lax.all_gather(mine, axis_name)           # (n, L)


def need_ids_local(need_ids, part):
    """(n_ps, n, L) per-PS **local-row** need lists from a PS-linearized
    (n, L) ``need_ids`` (PAD = -1): row ``[p, j]`` holds the local rows of
    shard ``p`` that worker ``j`` needs — exactly the pull/push list each
    parameter server receives, so a PS only ever addresses its own rows.
    Rows stay sorted-unique with PAD = -1, like :func:`need_ids_list`."""
    imax = jnp.iinfo(jnp.int32).max
    shard = part.shard_of_linear(need_ids)
    local = need_ids - shard * part.max_rows             # valid slots only
    out = []
    for p in range(part.n_ps):
        vals = jnp.where((need_ids >= 0) & (shard == p), local, imax)
        vals = jnp.sort(vals, axis=1)
        out.append(jnp.where(vals == imax, -1, vals))
    return jnp.stack(out).astype(jnp.int32)              # (n_ps, n, L)
