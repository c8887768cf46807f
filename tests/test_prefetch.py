"""Window-driven oracle prefetch: exact eviction, staging plane, split.

Contracts under test:
  * first/last-use-exact eviction (``EvictPlan``): a row with a pending
    use inside the window is evicted only after every unprotected
    candidate (property-tested directly on ``_select_victims``), the
    full-horizon plan reproduces the textbook Belady/OPT miss count on
    synthetic n=1 traces (any farthest-next-use tie-break is optimal, so
    miss counts match exactly), an empty plan is bitwise the
    no-protect scan (the W=0 degrade), and the dense and sparse engines
    agree under real plans including per-PS capacity budgets;
  * ``esd_reassign`` repairs a stale assignment without touching
    unflagged rows, respects the capacity cap, and is bitwise the
    identity when nothing changed;
  * the ``staged_gather`` Pallas kernel merges selected table rows into
    the carried plane exactly (PAD rows pass through bitwise, embedding
    widths that need block padding included);
  * the prefetch plane: candidate ranking/expiry stamping, budgeted
    staging into dead slots, residency/duplicate skips, expiry refresh,
    reclamation, the codec wire-format path, and the rowwise-adagrad
    freshness invariant (a staged row of an untrained id stays bitwise
    equal to the canonical table);
  * driver + simulator integration: per-step prefetch metrics appear and
    the loss trajectory is bitwise invariant to enabling prefetch; the
    simulator's prefetched/demand split sums to its miss count and the
    ``prefetch`` flag never changes transmission accounting.
"""
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, "tests")
from hypothesis import given, settings, strategies as st

from repro.core.cache import ClusterCache, EvictPlan, SparseClusterCache
from repro.core.dispatch_tpu import esd_reassign
from repro.core.simulator import SimConfig, simulate
from repro.data.synthetic import WORKLOADS
from repro.kernels.emb_lookup import staged_gather
from repro.pipeline import (prefetch_candidates, prefetch_init,
                            prefetch_step, staged_membership, window_meta)
from repro.ps import make_partition
from repro.quant.codecs import fake_quant, get_codec


def _trace(rng, V, T, width):
    """T batches of sorted-unique ids over [0, V)."""
    return [np.unique(rng.integers(0, V, int(rng.integers(1, width + 1))))
            for _ in range(T)]


def _plan_for(batches, t):
    """The exact plan delivered with step t: window = remaining stream."""
    return EvictPlan.from_window(window_meta(batches[t + 1:]))


def _belady_ref(batches, cap):
    """Textbook Belady/OPT miss count with the engine's batch pinning:
    all of step t's ids become resident, evictions (on overflow) pick
    the non-pinned id reused farthest in the future (never-again = +inf).
    """
    cache, miss = set(), 0
    for t, b in enumerate(batches):
        need = set(int(x) for x in b)
        miss += len(need - cache)
        cache |= need
        over = len(cache) - cap
        if over > 0:
            def nxt(u):
                for t2 in range(t + 1, len(batches)):
                    if u in batches[t2]:
                        return t2
                return len(batches) + 1
            victims = sorted(cache - need, key=lambda u: (-nxt(u), u))[:over]
            cache -= set(victims)
    return miss


# --------------------------------------------------------------------------
# exact eviction plan
# --------------------------------------------------------------------------
class TestEvictPlanExact:
    @settings(max_examples=25, deadline=None)
    @given(st.integers(0, 2 ** 31 - 1))
    def test_pending_use_evicted_last(self, seed):
        """Protected (in-plan, latest) candidates are chosen only once
        the unprotected pool is exhausted — exactly count - n_unprot of
        them, never more."""
        rng = np.random.default_rng(seed)
        V = 40
        cache = ClusterCache(2, V, 16, policy="lru")
        present = rng.random(V) < 0.6
        if not present.any():
            return
        cache.present[0] = present
        cache.latest[0] = present & (rng.random(V) < 0.8)
        cache.last_access[0] = rng.integers(0, 10, V).astype(np.int32)
        cand = np.where(present)[0]
        plan_ids = np.sort(rng.choice(V, size=12, replace=False))
        plan = EvictPlan(uids=plan_ids.astype(np.int64),
                         next_use=rng.integers(0, 6, 12).astype(np.int64),
                         last_use=rng.integers(0, 6, 12).astype(np.int64))
        count = int(rng.integers(1, len(cand) + 1))
        victims = cache._select_victims(0, cand, cand, count, protect=plan)
        prot = np.isin(cand, plan_ids) & cache.latest[0, cand]
        n_unprot = int((~prot).sum())
        n_prot_victims = int(np.isin(victims, cand[prot]).sum())
        assert n_prot_victims == max(0, count - n_unprot)

    @settings(max_examples=25, deadline=None)
    @given(st.integers(0, 2 ** 31 - 1), st.integers(3, 8),
           st.integers(4, 12))
    def test_full_horizon_plan_matches_belady(self, seed, cap, T):
        """n=1 trace: stepping the engine under the remaining-stream plan
        pays exactly the OPT miss count (tie-breaks differ, but every
        farthest-next-use policy is optimal, so the counts must agree)."""
        rng = np.random.default_rng(seed)
        V = 20
        batches = _trace(rng, V, T, width=cap)
        for engine_cls in (ClusterCache, SparseClusterCache):
            cache = engine_cls(1, V, cap, policy="lru")
            total = sum(
                int(cache.step([b], protect=_plan_for(batches, t))
                    .miss_pull.sum())
                for t, b in enumerate(batches))
            assert total == _belady_ref(batches, cap), engine_cls

    def test_empty_plan_bitwise_no_protect(self, rng):
        """W=0 degrade: an empty EvictPlan is the unchanged no-protect
        victim scan — identical planes and identical stats."""
        V, cap, T = 30, 8, 6
        batches = [[np.unique(rng.integers(0, V, 7)) for _ in range(2)]
                   for _ in range(T)]
        empty = EvictPlan.from_window(window_meta([]))
        a = ClusterCache(2, V, cap, policy="lru")
        b = ClusterCache(2, V, cap, policy="lru")
        for bt in batches:
            sa = a.step(bt, protect=None)
            sb = b.step(bt, protect=empty)
            for f in ("miss_pull", "update_push", "evict_push", "hits",
                      "miss_prefetched", "miss_demand"):
                np.testing.assert_array_equal(getattr(sa, f),
                                              getattr(sb, f), f)
        for f in ("present", "latest", "dirty"):
            np.testing.assert_array_equal(getattr(a, f), getattr(b, f), f)

    def test_dense_sparse_engines_agree_under_plan(self, rng):
        V, cap, n, T = 40, 10, 2, 6
        stream = [[np.unique(rng.integers(0, V, 8)) for _ in range(n)]
                  for _ in range(T)]
        plans = [EvictPlan.from_window(window_meta(
            [np.concatenate(bt) for bt in stream[t + 1: t + 4]]))
            for t in range(T)]
        dense = ClusterCache(n, V, cap, policy="lru")
        sparse = SparseClusterCache(n, V, cap, policy="lru")
        for t in range(T):
            sd = dense.step(stream[t], protect=plans[t])
            ss = sparse.step(stream[t], protect=plans[t])
            for f in ("miss_pull", "update_push", "evict_push", "hits",
                      "miss_prefetched", "miss_demand"):
                np.testing.assert_array_equal(getattr(sd, f),
                                              getattr(ss, f), f)
        for f in ("present", "latest", "dirty"):
            np.testing.assert_array_equal(getattr(dense, f),
                                          getattr(sparse, f), f)

    def test_per_ps_budget_split_arithmetic(self, rng):
        V, n, n_ps, T = 60, 2, 2, 5
        part = make_partition(V, n_ps)
        Vs = part.linear_size
        cache = SparseClusterCache(n, Vs, [8, 8], policy="lru", part=part)
        stream = [[np.unique(part.to_linear(rng.integers(0, V, 8)))
                   for _ in range(n)] for _ in range(T)]
        for t in range(T):
            wm = window_meta([np.concatenate(bt)
                              for bt in stream[t + 1: t + 4]])
            # window ids are already linear here; from_window keeps them
            stats = cache.step(stream[t],
                               protect=EvictPlan.from_window(wm))
            np.testing.assert_array_equal(
                stats.miss_prefetched + stats.miss_demand, stats.miss_pull)
            np.testing.assert_array_equal(
                stats.miss_prefetched_ps.sum(axis=1), stats.miss_prefetched)
            np.testing.assert_array_equal(
                stats.miss_demand_ps.sum(axis=1), stats.miss_demand)
        # post-warmup, the full-stream window announces every miss
        assert stats.miss_prefetched.sum() > 0

    def test_linearize_resorts(self):
        part = make_partition(50, 2)
        uids = np.arange(0, 50, 7, dtype=np.int64)
        plan = EvictPlan(uids=uids, next_use=np.arange(len(uids)),
                         last_use=np.arange(len(uids)))
        lin = plan.linearize(part)
        assert (np.diff(lin.uids) > 0).all()
        back = {int(u): int(nx) for u, nx in zip(
            part.to_linear(uids), plan.next_use)}
        for u, nx in zip(lin.uids, lin.next_use):
            assert back[int(u)] == int(nx)


# --------------------------------------------------------------------------
# stale-assignment repair
# --------------------------------------------------------------------------
class TestReassign:
    @settings(max_examples=20, deadline=None)
    @given(st.integers(0, 2 ** 31 - 1), st.integers(2, 5))
    def test_repair_invariants(self, seed, n):
        rng = np.random.default_rng(seed)
        k = int(rng.integers(n, 4 * n))
        cap = -(-k // n) + int(rng.integers(0, 3))
        C = rng.random((k, n)).astype(np.float32)
        # a feasible stale assignment (round-robin respects cap)
        assign = np.arange(k, dtype=np.int32) % n
        flagged = rng.random(k) < 0.4
        a2, n_re = esd_reassign(jnp.asarray(C), jnp.asarray(assign),
                                jnp.asarray(flagged), cap)
        a2 = np.asarray(a2)
        assert int(n_re) == int(flagged.sum())
        np.testing.assert_array_equal(a2[~flagged], assign[~flagged])
        assert ((a2 >= 0) & (a2 < n)).all()
        assert np.bincount(a2, minlength=n).max() <= cap

    def test_no_flags_is_identity(self, rng):
        k, n, cap = 9, 3, 4
        C = rng.random((k, n)).astype(np.float32)
        assign = rng.integers(0, n, k).astype(np.int32)
        a2, n_re = esd_reassign(jnp.asarray(C), jnp.asarray(assign),
                                jnp.zeros(k, bool), cap)
        np.testing.assert_array_equal(np.asarray(a2), assign)
        assert int(n_re) == 0


# --------------------------------------------------------------------------
# staged-gather kernel
# --------------------------------------------------------------------------
class TestStagedGather:
    @settings(max_examples=10, deadline=None)
    @given(st.integers(0, 2 ** 31 - 1), st.sampled_from([16, 32, 37]))
    def test_matches_oracle(self, seed, E):
        rng = np.random.default_rng(seed)
        C, V = 12, 30
        plane = rng.standard_normal((C, E)).astype(np.float32)
        table = rng.standard_normal((V, E)).astype(np.float32)
        src = np.where(rng.random(C) < 0.5,
                       rng.integers(0, V, C), -1).astype(np.int32)
        out = np.asarray(staged_gather(jnp.asarray(plane),
                                       jnp.asarray(table),
                                       jnp.asarray(src), block_e=16))
        ref = np.where(src[:, None] >= 0, table[np.clip(src, 0, V - 1)],
                       plane)
        np.testing.assert_array_equal(out, ref)

    def test_all_pad_is_identity(self, rng):
        plane = rng.standard_normal((6, 24)).astype(np.float32)
        table = rng.standard_normal((10, 24)).astype(np.float32)
        out = staged_gather(jnp.asarray(plane), jnp.asarray(table),
                            jnp.full((6,), -1, jnp.int32))
        np.testing.assert_array_equal(np.asarray(out), plane)


# --------------------------------------------------------------------------
# prefetch plane
# --------------------------------------------------------------------------
class TestPrefetchPlane:
    V, E = 32, 8

    def _table(self, rng):
        return jnp.asarray(rng.standard_normal((self.V, self.E))
                           .astype(np.float32))

    def test_candidates_rank_and_expiry(self):
        meta = window_meta([np.array([5, 9]), np.array([2, 5]),
                            np.array([7])])
        ids, exp = prefetch_candidates(meta, step=10, max_cands=6)
        # urgency order: first-use 0 ids (5, 9) before 2 (first use 1)
        assert ids[:2].tolist() in ([5, 9], [9, 5])
        assert set(ids[2:4].tolist()) == {2, 7}
        assert ids[4:].tolist() == [-1, -1]
        by = dict(zip(ids.tolist(), exp.tolist()))
        assert by[5] == 10 + 1 + 1      # last use = window batch 1
        assert by[9] == 10 + 1 + 0
        assert by[7] == 10 + 1 + 2
        # truncation keeps the most urgent
        ids2, _ = prefetch_candidates(meta, step=10, max_cands=2)
        assert set(ids2.tolist()) <= {5, 9}

    def test_stage_budget_and_membership(self, rng):
        table = self._table(rng)
        plane = prefetch_init(8, self.E)
        cids = np.full(6, -1, np.int32)
        cexp = np.full(6, -1, np.int32)
        cids[:4] = [3, 11, 4, 20]
        cexp[:4] = [5, 6, 5, 9]
        resident = jnp.zeros((self.V,), bool).at[11].set(True)
        plane, n = prefetch_step(plane, table, resident,
                                 jnp.asarray(cids), jnp.asarray(cexp),
                                 0, budget=2)
        # budget 2 of the 3 non-resident candidates, urgency order
        assert int(n) == 2
        memb = np.asarray(staged_membership(plane, self.V, 1))
        assert memb[[3, 4]].all() and not memb[[11, 20]].any()
        # staged rows are bitwise the canonical table rows
        ids = np.asarray(plane.ids)
        for s in np.where(ids >= 0)[0]:
            np.testing.assert_array_equal(np.asarray(plane.rows)[s],
                                          np.asarray(table)[ids[s]])

    def test_refresh_reclaim_and_dup_skip(self, rng):
        table = self._table(rng)
        plane = prefetch_init(4, self.E)
        cids = np.array([7, -1, -1], np.int32)
        cexp = np.array([2, -1, -1], np.int32)
        plane, n0 = prefetch_step(plane, table, jnp.zeros((self.V,), bool),
                                  jnp.asarray(cids), jnp.asarray(cexp),
                                  0, budget=4)
        assert int(n0) == 1
        # same id again with a later expiry: refresh, no re-pull
        cexp2 = np.array([5, -1, -1], np.int32)
        plane, n1 = prefetch_step(plane, table, jnp.zeros((self.V,), bool),
                                  jnp.asarray(cids), jnp.asarray(cexp2),
                                  1, budget=4)
        assert int(n1) == 0
        assert np.asarray(staged_membership(plane, self.V, 4))[7]
        # past the refreshed expiry the slot dies and is reusable
        assert not np.asarray(staged_membership(plane, self.V, 6))[7]
        cids3 = np.array([9, -1, -1], np.int32)
        cexp3 = np.array([8, -1, -1], np.int32)
        plane, n2 = prefetch_step(plane, table, jnp.zeros((self.V,), bool),
                                  jnp.asarray(cids3), jnp.asarray(cexp3),
                                  6, budget=4)
        assert int(n2) == 1
        memb = np.asarray(staged_membership(plane, self.V, 6))
        assert memb[9] and not memb[7]

    def test_codec_path_holds_wire_rows(self, rng):
        table = self._table(rng)
        plane = prefetch_init(4, self.E)
        cids = np.array([3, 12, -1, -1], np.int32)
        cexp = np.array([4, 4, -1, -1], np.int32)
        plane, n = prefetch_step(plane, table, jnp.zeros((self.V,), bool),
                                 jnp.asarray(cids), jnp.asarray(cexp),
                                 0, budget=4, codec="int8")
        assert int(n) == 2
        c = get_codec("int8")
        ids = np.asarray(plane.ids)
        for s in np.where(ids >= 0)[0]:
            np.testing.assert_allclose(
                np.asarray(plane.rows)[s],
                np.asarray(fake_quant(table[ids[s]][None, :], c))[0],
                atol=1e-5)

    def test_staged_rows_fresh_under_rowwise_adagrad(self, rng):
        """The freshness invariant behind serving-from-plane: an id that
        receives no gradient keeps its table row bitwise unchanged, so
        its staged copy never goes stale."""
        from repro.optim import get_optimizer

        opt = get_optimizer("rowwise_adagrad", 1e-2)
        table = self._table(rng)
        params = {"embed": table}
        state = opt.init(params)
        grads = {"embed": jnp.zeros_like(table).at[2].set(1.0)}
        new_params, _ = opt.update(grads, state, params)
        touched = np.zeros(self.V, bool)
        touched[2] = True
        np.testing.assert_array_equal(
            np.asarray(new_params["embed"])[~touched],
            np.asarray(table)[~touched])
        assert not np.array_equal(np.asarray(new_params["embed"])[2],
                                  np.asarray(table)[2])


# --------------------------------------------------------------------------
# driver + simulator integration
# --------------------------------------------------------------------------
class TestDriverPrefetch:
    def test_metrics_and_loss_invariance(self):
        from repro.launch.train import main

        common = ["--arch", "wdl-tiny", "--steps", "4",
                  "--batch-per-worker", "8", "--esd-alpha", "0",
                  "--capacity-ratio", "0.3", "--pipeline-depth", "2",
                  "--lookahead", "2"]
        base = main(common)
        pf = main(common + ["--prefetch", "16", "--prefetch-slots", "64"])
        assert [r["loss"] for r in base] == [r["loss"] for r in pf]
        assert [r["miss_pull"] for r in base] == \
            [r["miss_pull"] for r in pf]
        for r in pf:
            assert {"prefetch_bytes", "demand_miss_bytes",
                    "prefetch_hit_rate"} <= set(r)
        assert sum(r["prefetch_bytes"] for r in pf) > 0
        # with staging live, some misses leave the demand path
        assert sum(r["demand_miss_bytes"] for r in pf) < \
            sum(r["demand_miss_bytes"] for r in base)

    def test_guards(self):
        from repro.launch.train import main

        base = ["--arch", "wdl-tiny", "--steps", "1",
                "--batch-per-worker", "8", "--esd-alpha", "0"]
        with pytest.raises(SystemExit):   # prefetch needs a window
            main(base + ["--prefetch", "8"])
        with pytest.raises(SystemExit):   # decide-ahead vs stale-decide
            main(base + ["--pipeline-depth", "2", "--decide-ahead", "1",
                         "--stale-decide"])
        with pytest.raises(SystemExit):   # budget > slots
            main(base + ["--lookahead", "2", "--prefetch", "64",
                         "--prefetch-slots", "8"])


class TestSimulatorPrefetch:
    BASE = dict(n_workers=4, batch_per_worker=16, iters=10, warmup=2,
                mechanism="esd", alpha=0.0, cache_ratio=0.3, policy="lru",
                lookahead=3)

    def test_split_sums_and_accounting_invariance(self):
        wl = WORKLOADS["tiny"]
        r = simulate(SimConfig(workload=wl, prefetch=False, **self.BASE))
        rp = simulate(SimConfig(workload=wl, prefetch=True, **self.BASE))
        for k in ("miss_pull_total", "miss_prefetched_total",
                  "miss_demand_total"):
            assert rp.pipeline[k] == r.pipeline[k], k
        assert (rp.pipeline["miss_prefetched_total"]
                + rp.pipeline["miss_demand_total"]
                == rp.pipeline["miss_pull_total"])
        np.testing.assert_array_equal(r.per_iter_cost, rp.per_iter_cost)
        assert rp.pipeline["prefetch"] and not r.pipeline["prefetch"]

    def test_guards(self):
        wl = WORKLOADS["tiny"]
        with pytest.raises(ValueError):   # prefetch needs a window
            simulate(SimConfig(workload=wl, prefetch=True,
                               **{**self.BASE, "lookahead": 0}))
