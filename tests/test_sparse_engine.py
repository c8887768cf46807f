"""Touched-ids sparse engine == dense reference, across every layer.

The sparse engine's contract is *exact* equivalence:
  * cost: cost_matrix_sparse is bitwise-equal to cost_matrix_np (shared
    arithmetic); the jnp/Pallas variants match to float32 tolerance;
  * cache: SparseClusterCache reproduces ClusterCache's counts AND planes
    over multi-iteration traces (all policies, both sync modes);
  * in-jit state: esd_state_update_sparse reproduces esd_state_update's
    counts/planes including the bounded-candidate LRU cut, and its cut's
    stamp hit test reproduces the binary search it replaced;
  * simulator: engine="sparse" and engine="dense" produce identical
    SimResults (identical assignments -> identical transmission costs).
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    ClusterCache,
    SimConfig,
    SparseClusterCache,
    cost_matrix_jnp,
    cost_matrix_np,
    cost_matrix_sparse,
    cost_matrix_sparse_jnp,
    simulate,
)
from repro.core.dispatch_tpu import (
    esd_init,
    esd_sparse_init,
    esd_state_update,
    esd_state_update_sparse,
)
from repro.elastic import mask_state
from repro.kernels import cost_matrix_pallas, cost_matrix_pallas_sparse
from repro.ps import make_partition


def _instance(rng, n=4, V=200, k=16, F=6, pad_frac=0.15, dup=True):
    latest = rng.random((n, V)) > 0.5
    dirty = (rng.random((n, V)) > 0.7) & latest
    t = rng.random(n) * 1e-5 + 1e-6          # heterogeneous t_tran
    samples = rng.integers(0, V, (k, F))
    if dup:  # force duplicate ids inside samples
        samples[:, 1] = samples[:, 0]
    samples[rng.random((k, F)) < pad_frac] = -1
    return samples, latest, dirty, t


class TestCostEquivalence:
    def test_sparse_bitwise_equals_np(self, rng):
        s, latest, dirty, t = _instance(rng)
        a = cost_matrix_np(s, latest, dirty, t)
        b = cost_matrix_sparse(s, latest, dirty, t)
        assert (a == b).all()

    @pytest.mark.parametrize("fn", [cost_matrix_sparse_jnp, cost_matrix_jnp,
                                    cost_matrix_pallas,
                                    cost_matrix_pallas_sparse])
    def test_jnp_variants_match_np(self, rng, fn):
        s, latest, dirty, t = _instance(rng)
        want = cost_matrix_np(s, latest, dirty, t)
        got = fn(jnp.asarray(s), jnp.asarray(latest), jnp.asarray(dirty),
                 jnp.asarray(t))
        np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5,
                                   atol=1e-10)

    def test_all_pad_batch(self):
        s = np.full((3, 4), -1)
        latest = np.zeros((2, 10), bool)
        dirty = np.zeros((2, 10), bool)
        t = np.ones(2)
        np.testing.assert_array_equal(
            cost_matrix_sparse(s, latest, dirty, t), np.zeros((3, 2)))
        got = cost_matrix_sparse_jnp(jnp.asarray(s), jnp.asarray(latest),
                                     jnp.asarray(dirty), jnp.asarray(t))
        np.testing.assert_array_equal(np.asarray(got), np.zeros((3, 2)))

    def test_duplicate_ids_count_once_sparse(self):
        latest = np.zeros((2, 10), bool)
        dirty = np.zeros((2, 10), bool)
        t = np.ones(2)
        C_dup = cost_matrix_sparse(np.array([[3, 3, 3, -1]]), latest, dirty, t)
        C_one = cost_matrix_sparse(np.array([[3, -1, -1, -1]]), latest, dirty, t)
        np.testing.assert_array_equal(C_dup, C_one)

    @pytest.mark.parametrize("fn", [cost_matrix_np, cost_matrix_sparse,
                                    cost_matrix_sparse_jnp, cost_matrix_jnp])
    def test_id_zero_after_pad_counts(self, fn):
        """Regression: PAD slots used to clamp to 0 *before* dedup, so a
        real id 0 preceded by a PAD in the same sample was dropped."""
        latest = np.zeros((2, 10), bool)
        dirty = np.zeros((2, 10), bool)
        t = np.array([1.0, 2.0])
        C = np.asarray(fn(jnp.asarray(np.array([[-1, 0, 5]])),
                          jnp.asarray(latest), jnp.asarray(dirty),
                          jnp.asarray(t)))
        np.testing.assert_allclose(C, [[2.0, 4.0]])   # two misses, not one


STATE_FIELDS = ("present", "latest", "dirty", "freq", "last_access", "mark",
                "target")
STAT_FIELDS = ("miss_pull", "update_push", "evict_push", "lookups", "hits")


class TestCacheEquivalence:
    @pytest.mark.parametrize("policy", ["emark", "lru", "lfu"])
    @pytest.mark.parametrize("sync", ["on_demand", "eager"])
    def test_trace_identical(self, policy, sync):
        n, V, cap = 3, 60, 8
        dense = ClusterCache(n, V, cap, policy=policy, sync=sync)
        sparse = SparseClusterCache(n, V, cap, policy=policy, sync=sync)
        r = np.random.default_rng(7)
        for it in range(25):
            batches = [r.choice(V, r.integers(0, 7), replace=False)
                       for _ in range(n)]
            sd, ss = dense.step(batches), sparse.step(batches)
            for f in STAT_FIELDS:
                np.testing.assert_array_equal(
                    getattr(sd, f), getattr(ss, f),
                    err_msg=f"{policy}/{sync} it{it} {f}")
            for f in STATE_FIELDS:
                np.testing.assert_array_equal(
                    getattr(dense, f), getattr(sparse, f),
                    err_msg=f"{policy}/{sync} it{it} {f}")

    def test_prefill_identical(self):
        dense = ClusterCache(2, 40, 10)
        sparse = SparseClusterCache(2, 40, 10)
        hot = np.arange(25)
        dense.prefill(hot)
        sparse.prefill(hot)
        r = np.random.default_rng(3)
        for _ in range(10):
            batches = [r.choice(40, 5, replace=False) for _ in range(2)]
            sd, ss = dense.step(batches), sparse.step(batches)
            for f in STAT_FIELDS:
                np.testing.assert_array_equal(getattr(sd, f), getattr(ss, f))
        for f in STATE_FIELDS:
            np.testing.assert_array_equal(getattr(dense, f),
                                          getattr(sparse, f))


class TestStateUpdateEquivalence:
    # jitted with static capacity: the 20-iteration traces reuse one
    # compiled step instead of paying per-op eager dispatch every
    # iteration (same bitwise outputs — the engines are jit-compatible by
    # contract)
    _dense_step = staticmethod(jax.jit(esd_state_update, static_argnums=2))
    _sparse_step = staticmethod(
        jax.jit(esd_state_update_sparse, static_argnums=2))

    def _trace(self, capacity, iters=20, n=3, V=50, L=8, seed=5):
        dstate = esd_init(n, V)
        sstate = esd_sparse_init(n, V, capacity, L)
        r = np.random.default_rng(seed)
        for it in range(iters):
            need = np.zeros((n, V), bool)
            ids_list = np.full((n, L), -1, np.int32)
            for j in range(n):
                ids = np.sort(r.choice(V, r.integers(0, L + 1), replace=False))
                need[j, ids] = True
                ids_list[j, :len(ids)] = ids
            dstate, dc = self._dense_step(dstate, jnp.asarray(need), capacity)
            sstate, sc = self._sparse_step(sstate, jnp.asarray(ids_list),
                                           capacity)
            for key in dc:
                np.testing.assert_array_equal(
                    np.asarray(dc[key]), np.asarray(sc[key]),
                    err_msg=f"it{it} {key}")
            for f in ("latest", "dirty", "last_access"):
                np.testing.assert_array_equal(
                    np.asarray(getattr(dstate, f)),
                    np.asarray(getattr(sstate, f)), err_msg=f"it{it} {f}")

    def test_no_capacity(self):
        self._trace(capacity=None)

    def test_lru_capacity(self):
        self._trace(capacity=10)

    def test_tight_capacity(self):
        # capacity == max batch: every iteration cuts
        self._trace(capacity=8, L=8, seed=11)

    def test_undersized_slots_raises(self):
        state = esd_sparse_init(2, 30)          # no slot buffer
        need = jnp.zeros((2, 4), jnp.int32)
        with pytest.raises(ValueError):
            esd_state_update_sparse(state, need, capacity=5)

    def test_lru_key_no_overflow_at_paper_scale(self):
        """A packed last_access*V + id recency key wraps int32 once
        step >= 2^31/V (x64 is disabled); the two-key lexicographic cut
        must still evict the true LRU victim at V = 1e6, step > 2147."""
        V, cap, L = 1_000_000, 2, 2
        start = jnp.asarray(2_999, jnp.int32)    # past the wrap point
        dstate = dataclasses.replace(esd_init(1, V), step=start)
        sstate = dataclasses.replace(esd_sparse_init(1, V, cap, L),
                                     step=start)
        trace = [np.array([[10, 20]], np.int32),     # step 3000: fill
                 np.array([[30, -1]], np.int32)]     # step 3001: evict one
        for ids in trace:
            need = np.zeros((1, V), bool)
            need[0, ids[ids >= 0]] = True
            dstate, dc_ = self._dense_step(dstate, jnp.asarray(need), cap)
            sstate, sc_ = self._sparse_step(sstate, jnp.asarray(ids), cap)
            for key in dc_:
                np.testing.assert_array_equal(np.asarray(dc_[key]),
                                              np.asarray(sc_[key]))
        for st in (dstate, sstate):
            lat = np.asarray(st.latest[0])
            # id 10 loses the (la, id) tie against 20; 30 is newest
            assert not lat[10] and lat[20] and lat[30], \
                np.where(lat)[0].tolist()


class TestSparseEdgeCases:
    """Degenerate inputs where the sparse engine's compaction tricks
    (unique/searchsorted universes, candidate zones) are most fragile:
    empty batches, maximal contention on one id, and a zero-size cache."""

    def _compare(self, capacity, traces, n=3, V=40, L=4):
        dstate = esd_init(n, V)
        sstate = esd_sparse_init(n, V, capacity, L)
        dense = TestStateUpdateEquivalence._dense_step
        sparse = TestStateUpdateEquivalence._sparse_step
        for it, ids_list in enumerate(traces):
            need = np.zeros((n, V), bool)
            for j in range(n):
                need[j, ids_list[j][ids_list[j] >= 0]] = True
            dstate, dc = dense(dstate, jnp.asarray(need), capacity)
            sstate, sc = sparse(sstate, jnp.asarray(ids_list), capacity)
            for key in dc:
                np.testing.assert_array_equal(
                    np.asarray(dc[key]), np.asarray(sc[key]),
                    err_msg=f"it{it} {key}")
            for f in ("latest", "dirty", "last_access"):
                np.testing.assert_array_equal(
                    np.asarray(getattr(dstate, f)),
                    np.asarray(getattr(sstate, f)), err_msg=f"it{it} {f}")

    def test_all_pad_rows(self):
        """A batch where no worker touches anything (all PAD): no counts,
        no state change, and with capacity the survivors stay put."""
        n, L = 3, 4
        warm = np.array([[0, 1, 2, -1], [3, 4, -1, -1], [5, -1, -1, -1]],
                        np.int32)
        pad = np.full((n, L), -1, np.int32)
        for capacity in (None, 4):
            self._compare(capacity, [warm, pad, pad, warm])

    def test_single_id_touched_by_every_shard(self):
        """Maximal contention: all workers train the same single id every
        iteration — phases A/B/C all hit the multi-pusher branch."""
        n, L = 3, 4
        one = np.full((n, L), -1, np.int32)
        one[:, 0] = 7
        other = np.full((n, L), -1, np.int32)
        other[:, 0] = 9
        for capacity in (None, 2):
            self._compare(capacity, [one, one, other, one])

    def test_capacity_zero(self):
        """capacity=0: nothing survives past its own iteration — the keep
        set is exactly the pinned current ids."""
        n, L = 2, 3
        a = np.array([[0, 1, -1], [2, -1, -1]], np.int32)
        b = np.array([[1, -1, -1], [0, 2, -1]], np.int32)
        pad = np.full((n, L), -1, np.int32)
        self._compare(0, [a, b, pad, a], n=n)
        # and nothing is resident after a cut with an empty batch
        dstate = esd_init(n, 10)
        sstate = esd_sparse_init(n, 10, 0, L)
        dense = TestStateUpdateEquivalence._dense_step
        sparse = TestStateUpdateEquivalence._sparse_step
        dstate, _ = dense(dstate, jnp.asarray(np.eye(n, 10, dtype=bool)), 0)
        sstate, _ = sparse(
            sstate, jnp.asarray(np.arange(n)[:, None].astype(np.int32)
                                * np.ones((1, L), np.int32)
                                * (np.arange(L) == 0) - (np.arange(L) != 0)),
            0)
        dstate, _ = dense(dstate, jnp.zeros((n, 10), bool), 0)
        sstate, _ = sparse(sstate, jnp.full((n, L), -1, jnp.int32), 0)
        assert not np.asarray(dstate.latest).any()
        assert not np.asarray(sstate.latest).any()

    def test_cost_single_id_every_row(self):
        """Cost matrix: every sample is the same single id — dedup inside
        the row must count it once, and all rows are identical."""
        n, V = 3, 30
        latest = np.zeros((n, V), bool)
        latest[1, 7] = True
        dirty = np.zeros((n, V), bool)
        dirty[1, 7] = True
        t = np.array([1.0, 2.0, 4.0])
        s = np.full((5, 4), 7, np.int64)
        want = cost_matrix_np(s, latest, dirty, t)
        got = cost_matrix_sparse(s, latest, dirty, t)
        np.testing.assert_array_equal(got, want)
        got_jnp = cost_matrix_sparse_jnp(jnp.asarray(s), jnp.asarray(latest),
                                         jnp.asarray(dirty), jnp.asarray(t))
        np.testing.assert_allclose(np.asarray(got_jnp), want, rtol=1e-6)
        assert (want == want[0]).all()      # identical rows


def _search_hit(need, slots):
    """The cut's former hit test: each slot id looked up by binary search
    in its row's sorted need list (PAD -1)."""
    L = need.shape[1]
    need_sorted = jnp.sort(
        jnp.where(need >= 0, need, jnp.iinfo(jnp.int32).max), axis=1)
    pos = jnp.clip(jax.vmap(jnp.searchsorted)(need_sorted, slots), 0, L - 1)
    return ((jnp.take_along_axis(need_sorted, pos, axis=1) == slots)
            & (slots >= 0))


def _search_cut(latest, dirty, last_access, slots, need, step, cap):
    """One budget's LRU cut over ``need`` (n, L) and its slot segment,
    built on :func:`_search_hit`."""
    n, L = need.shape
    V = latest.shape[1]
    rows = jnp.arange(n)[:, None]
    cand = jnp.concatenate(
        [need, jnp.where(_search_hit(need, slots), -1, slots)], axis=1)
    la_c = jnp.where(cand >= 0, last_access[rows, jnp.clip(cand, 0, V - 1)],
                     -1)
    sla, sid = jax.lax.sort((la_c, cand), dimension=1, num_keys=2)
    T, S = cand.shape[1], slots.shape[1]
    zone = slice(T - cap - 2 * L, T - cap)
    ev = (sla[:, zone] >= 0) & (sla[:, zone] < step)
    ev_ids = jnp.where(ev, sid[:, zone], V)
    egc = jnp.minimum(ev_ids, V - 1)
    pushed = (latest[rows, egc] & dirty[rows, egc] & ev).sum(axis=1)
    latest = latest.at[rows, ev_ids].set(False, mode="drop")
    dirty = dirty.at[rows, ev_ids].set(False, mode="drop")
    top_la, top_id = sla[:, T - S:], sid[:, T - S:]
    keep = (top_la >= 0) & ((jnp.arange(S) >= S - cap)[None, :]
                            | (top_la == step))
    return (latest, dirty, jnp.where(keep, top_id, -1),
            pushed.astype(jnp.int32))


def _search_step(state, need_ids, capacity, part):
    """esd_state_update_sparse with the cut rebuilt on the search: its
    phases (capacity=None leaves the slots alone), then the old cut."""
    mid, counts = esd_state_update_sparse(state, need_ids, None, part)
    L = need_ids.shape[1]
    valid = need_ids >= 0
    latest, dirty = mid.latest, mid.dirty
    if isinstance(capacity, tuple):
        shard = part.shard_of_linear(jnp.where(valid, need_ids, 0))
        segs, pushed, off = [], [], 0
        for p, cap in enumerate(capacity):
            latest, dirty, seg, ev = _search_cut(
                latest, dirty, mid.last_access,
                state.slots[:, off:off + cap + L],
                jnp.where(valid & (shard == p), need_ids, -1), mid.step, cap)
            segs.append(seg)
            pushed.append(ev)
            off += cap + L
        slots = jnp.concatenate(segs, axis=1)
        counts["evict_push"] = sum(pushed)
        counts["evict_push_ps"] = jnp.stack(pushed, axis=1)
    else:
        latest, dirty, slots, counts["evict_push"] = _search_cut(
            latest, dirty, mid.last_access, state.slots,
            jnp.where(valid, need_ids, -1), mid.step, capacity)
    return dataclasses.replace(mid, latest=latest, dirty=dirty,
                               slots=slots), counts


class TestStampHitTest:
    """The LRU cut tells a surviving slot that is needed again this step
    by its Phase-C stamp (last_access == step), not by searching the need
    list; state and counts stay bitwise those of the search."""
    _step = staticmethod(
        jax.jit(esd_state_update_sparse, static_argnums=(2, 3)))
    _search = staticmethod(jax.jit(_search_step, static_argnums=(2, 3)))

    @pytest.mark.parametrize("case", ["spill", "all_pad_rows",
                                      "row_in_slots", "worker_dead",
                                      "per_ps"])
    def test_state_and_counts_match_search(self, case):
        n, V, L, capacity, part = 3, 120, 12, 20, None
        if case == "spill":
            capacity = 5                   # batches of up to 12 ids pin over it
        if case == "per_ps":
            part = make_partition(V, 2)
            capacity = (9, 5)
        ids_space = (np.arange(V) if part is None
                     else np.asarray(part.to_linear(np.arange(V))))
        Vs = V if part is None else part.linear_size
        state = ref = esd_sparse_init(n, Vs, capacity, L)
        r = np.random.default_rng(17)
        evicted = 0
        for it in range(16):
            dead = case == "worker_dead" and 5 <= it < 9
            if dead:                       # worker 1 away, then a cold rejoin
                active = np.arange(n) != 1
                state, ref = mask_state(state, active), mask_state(ref, active)
            ids = np.full((n, L), -1, np.int32)
            for j in range(n):
                pad_row = case == "all_pad_rows" and (it % 4 == 1
                                                      or r.random() < 0.3)
                if pad_row or (dead and j == 1):
                    continue
                pool = ids_space
                if case == "row_in_slots" and j == 0 and it % 2:
                    pool = np.asarray(state.slots[0])
                    pool = pool[pool >= 0]
                    k = min(L, len(pool))
                else:
                    k = int(r.integers(0, L + 1))
                ids[j, r.choice(L, k, replace=False)] = r.choice(
                    pool, k, replace=False)
            state, c = self._step(state, jnp.asarray(ids), capacity, part)
            ref, c_ref = self._search(ref, jnp.asarray(ids), capacity, part)
            evicted += int(np.asarray(c_ref["evict_push"]).sum())
            assert c.keys() == c_ref.keys()
            for key in c:
                np.testing.assert_array_equal(
                    np.asarray(c[key]), np.asarray(c_ref[key]),
                    err_msg=f"{case} it{it} {key}")
            for f in ("slots", "latest", "dirty", "last_access", "step"):
                np.testing.assert_array_equal(
                    np.asarray(getattr(state, f)),
                    np.asarray(getattr(ref, f)), err_msg=f"{case} it{it} {f}")
        assert evicted > 0                 # the trace reaches the cut

    @pytest.mark.parametrize("budget", ["single", "per_ps"])
    def test_cut_adds_no_loop(self, budget):
        """The only loop left is the universe's searchsorted: lowering with
        a capacity gives as many stablehlo.while ops as without one."""
        n, V, L, capacity, part = 2, 64, 8, 10, None
        if budget == "per_ps":
            part = make_partition(V, 2)
            V, capacity = part.linear_size, (6, 4)
        step = jax.jit(esd_state_update_sparse, static_argnums=(2, 3))

        def n_while(cap):
            state = esd_sparse_init(n, V, cap, L)
            return step.lower(state, jnp.zeros((n, L), jnp.int32), cap,
                              part).as_text().count("stablehlo.while")

        assert n_while(capacity) == n_while(None)


class TestSimulatorEquivalence:
    # default tier-1 keeps the paper's mechanism as the representative;
    # the baseline-mechanism sweep runs in the slow tier (scripts/ci.sh
    # --slow) — same engines, heavier parameterization.
    @pytest.mark.parametrize(
        "mechanism",
        ["esd"] + [pytest.param(m, marks=pytest.mark.slow)
                   for m in ("het", "fae", "random")])
    def test_engines_identical(self, mechanism):
        from repro.data.synthetic import WORKLOADS
        cfg = SimConfig(workload=WORKLOADS["tiny"], n_workers=4,
                        batch_per_worker=8, iters=8, warmup=2,
                        mechanism=mechanism, engine="sparse")
        rs = simulate(cfg)
        rd = simulate(dataclasses.replace(cfg, engine="dense"))
        assert (rs.per_iter_cost == rd.per_iter_cost).all()
        assert rs.hit_ratio == rd.hit_ratio
        assert rs.ingredient == rd.ingredient

    @pytest.mark.slow
    def test_paper_scale_sparse_in_seconds(self):
        """V = 1e6, n = 16: the sparse engine keeps iterations batch-bound
        (this config used to be vocab-bound and impractical to simulate)."""
        import time

        from repro.data.synthetic import CTRWorkload
        wl = CTRWorkload(name="paper-scale", model="wdl",
                         table_sizes=(600_000, 300_000, 100_000),
                         zipf_a=(1.05, 1.1, 1.2))
        cfg = SimConfig(workload=wl, n_workers=16, batch_per_worker=32,
                        iters=12, warmup=2, alpha=0.0, engine="sparse")
        t0 = time.perf_counter()
        res = simulate(cfg)
        elapsed = time.perf_counter() - t0
        assert res.cost > 0
        assert elapsed < 60, f"paper-scale simulate took {elapsed:.1f}s"
