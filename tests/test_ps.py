"""repro.ps multi-parameter-server layer: translations, cost, state,
simulator, sharding, and the train driver.

Contracts under test:
  * PsPartition round-trips (property-tested over random partitions,
    both layouts, numpy and jnp callables);
  * n_ps=1 is the *bitwise* identity special case — the ps-aware cost
    paths reproduce the single-PS sparse engine exactly;
  * uniform per-PS bandwidths reproduce the single-PS cost matrix (up to
    float summation order across shards);
  * esd_state_update_sparse(part=...) leaves the state transition
    untouched and emits a per-(worker, PS) count breakdown that sums to
    the per-worker counts; dense/sparse cluster caches agree on it;
  * the simulator's ps path is bitwise-equal to the plain path at
    n_ps=1, and ESD beats random dispatch under skewed PS links;
  * the PS-stacked DLRM table is placement- and loss-equivalent to the
    flat table.
"""
import dataclasses
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, "tests")
from hypothesis import given, settings, strategies as st

from repro.core import (
    ClusterCache,
    SimConfig,
    SparseClusterCache,
    cost_matrix_sparse,
    cost_matrix_sparse_jnp,
    cost_matrix_sparse_ps,
    cost_matrix_sparse_ps_jnp,
    hetero_ps_bandwidths,
    simulate,
)
from repro.core.dispatch_tpu import (
    esd_sparse_init,
    esd_state_update_sparse,
    need_ids_local,
)
from repro.data.synthetic import WORKLOADS
from repro.launch.mesh import make_mesh
from repro.ps import PsPartition, make_partition


def _random_partition(rng, vocab, n_ps, layout):
    if layout == "hashed":
        return PsPartition.hashed(vocab, n_ps)
    if layout == "uneven":
        cuts = np.sort(rng.integers(0, vocab + 1, n_ps - 1))
        bounds = tuple(np.concatenate([[0], cuts, [vocab]]).tolist())
        return PsPartition.contiguous(vocab, n_ps, bounds)
    return PsPartition.contiguous(vocab, n_ps)


class TestPartitionRoundTrip:
    @settings(max_examples=30, deadline=None)
    @given(st.integers(1, 500), st.integers(1, 6), st.integers(0, 2),
           st.integers(0, 2 ** 31 - 1))
    def test_round_trip(self, vocab, n_ps, layout_i, seed):
        layout = ("contiguous", "hashed", "uneven")[layout_i]
        rng = np.random.default_rng(seed)
        part = _random_partition(rng, vocab, n_ps, layout)
        ids = rng.integers(-1, vocab, (64,))
        shard, local = part.global_to_local(ids)
        valid = ids >= 0
        # addresses are in-range: shard < n_ps, local < rows(shard)
        assert (shard[valid] >= 0).all() and (shard[valid] < part.n_ps).all()
        rows = np.array([part.rows(p) for p in range(part.n_ps)])
        assert (local[valid] >= 0).all()
        assert (local[valid] < rows[shard[valid]]).all()
        assert (local[~valid] == -1).all()
        # inverses
        np.testing.assert_array_equal(part.local_to_global(shard, local), ids)
        lin = part.to_linear(ids)
        assert (lin[~valid] == -1).all()
        assert lin.max(initial=-1) < part.linear_size
        np.testing.assert_array_equal(part.from_linear(lin), ids)
        # shard is recoverable from the linearized id
        np.testing.assert_array_equal(
            np.where(valid, part.shard_of_linear(lin), 0),
            np.where(valid, shard, 0))
        # translation is injective on valid ids
        u = np.unique(ids[valid])
        assert len(np.unique(part.to_linear(u))) == len(u)

    @settings(max_examples=10, deadline=None)
    @given(st.integers(1, 300), st.integers(1, 5), st.integers(0, 1),
           st.integers(0, 2 ** 31 - 1))
    def test_jnp_matches_np(self, vocab, n_ps, layout_i, seed):
        layout = ("contiguous", "hashed")[layout_i]
        rng = np.random.default_rng(seed)
        part = _random_partition(rng, vocab, n_ps, layout)
        ids = rng.integers(-1, vocab, (40,)).astype(np.int32)
        s_np, l_np = part.global_to_local(ids)
        s_j, l_j = part.global_to_local(jnp.asarray(ids))
        np.testing.assert_array_equal(np.asarray(s_j), s_np)
        np.testing.assert_array_equal(np.asarray(l_j), l_np)
        np.testing.assert_array_equal(
            np.asarray(part.to_linear(jnp.asarray(ids))), part.to_linear(ids))
        # and under jit, as a closed-over static partition
        lin = jax.jit(part.to_linear)(jnp.asarray(ids))
        np.testing.assert_array_equal(np.asarray(lin), part.to_linear(ids))

    def test_identity_is_identity(self):
        part = PsPartition.identity(123)
        ids = np.arange(-1, 123)
        assert part.to_linear(ids) is ids          # no-op, not a copy
        assert part.max_rows == 123 and part.linear_size == 123

    def test_bad_partitions_raise(self):
        with pytest.raises(ValueError):
            PsPartition(10, 0)
        with pytest.raises(ValueError):
            PsPartition.contiguous(10, 2, (0, 11, 10))
        with pytest.raises(ValueError):
            PsPartition(10, 2, "nope")


def _instance(rng, n=4, V=200, k=16, F=6):
    latest = rng.random((n, V)) > 0.5
    dirty = (rng.random((n, V)) > 0.7) & latest
    t = rng.random(n) * 1e-5 + 1e-6
    samples = rng.integers(0, V, (k, F))
    samples[:, 1] = samples[:, 0]                  # in-sample duplicates
    samples[rng.random((k, F)) < 0.15] = -1
    return samples, latest, dirty, t


def _lin_planes(part, latest, dirty):
    """Re-home (n, V) planes into the PS-linearized space."""
    n, V = latest.shape
    gl = np.asarray(part.to_linear(np.arange(V)))
    lat = np.zeros((n, part.linear_size), bool)
    dr = np.zeros((n, part.linear_size), bool)
    lat[:, gl] = latest
    dr[:, gl] = dirty
    return lat, dr


class TestPsCost:
    def test_nps1_bitwise_np(self, rng):
        s, latest, dirty, t = _instance(rng)
        part = PsPartition.identity(latest.shape[1])
        a = cost_matrix_sparse(s, latest, dirty, t)
        b = cost_matrix_sparse_ps(s, latest, dirty, t[:, None], part)
        assert (a == b).all()

    def test_nps1_bitwise_jnp(self, rng):
        s, latest, dirty, t = _instance(rng)
        part = PsPartition.identity(latest.shape[1])
        a = cost_matrix_sparse_jnp(jnp.asarray(s), jnp.asarray(latest),
                                   jnp.asarray(dirty), jnp.asarray(t))
        b = cost_matrix_sparse_ps_jnp(jnp.asarray(s), jnp.asarray(latest),
                                      jnp.asarray(dirty),
                                      jnp.asarray(t)[:, None], part)
        assert (np.asarray(a) == np.asarray(b)).all()

    @pytest.mark.parametrize("layout", ["contiguous", "hashed"])
    @pytest.mark.parametrize("n_ps", [2, 3, 4])
    def test_uniform_bandwidth_reproduces_single_ps(self, rng, n_ps, layout):
        """Column-constant t_ps must reproduce the single-PS Alg. 1 matrix
        (shards only regroup the float summation)."""
        s, latest, dirty, t = _instance(rng)
        V = latest.shape[1]
        part = make_partition(V, n_ps, layout)
        lat_lin, dr_lin = _lin_planes(part, latest, dirty)
        lin = part.to_linear(s)
        want = cost_matrix_sparse(s, latest, dirty, t)
        got = cost_matrix_sparse_ps(lin, lat_lin, dr_lin,
                                    np.repeat(t[:, None], n_ps, 1), part,
                                    linear=True)
        np.testing.assert_allclose(got, want, rtol=1e-12)
        got_j = cost_matrix_sparse_ps_jnp(
            jnp.asarray(lin), jnp.asarray(lat_lin), jnp.asarray(dr_lin),
            jnp.asarray(np.repeat(t[:, None], n_ps, 1)), part, linear=True)
        np.testing.assert_allclose(np.asarray(got_j), want, rtol=1e-5,
                                   atol=1e-10)

    def test_np_jnp_ps_agree(self, rng):
        s, latest, dirty, t = _instance(rng)
        V = latest.shape[1]
        part = make_partition(V, 3)
        lat_lin, dr_lin = _lin_planes(part, latest, dirty)
        lin = part.to_linear(s)
        t_ps = rng.random((latest.shape[0], 3)) * 1e-5 + 1e-6
        a = cost_matrix_sparse_ps(lin, lat_lin, dr_lin, t_ps, part,
                                  linear=True)
        b = cost_matrix_sparse_ps_jnp(jnp.asarray(lin), jnp.asarray(lat_lin),
                                      jnp.asarray(dr_lin), jnp.asarray(t_ps),
                                      part, linear=True)
        np.testing.assert_allclose(np.asarray(b), a, rtol=1e-5, atol=1e-10)

    def test_slow_shard_changes_dispatch(self, rng):
        """A miss homed on a slow shard must cost more than the same miss
        homed on a fast shard — the signal heterogeneous-PS dispatch uses."""
        V, n = 40, 2
        part = make_partition(V, 2)       # shard 0: [0, 20), shard 1: [20, 40)
        latest = np.zeros((n, part.linear_size), bool)
        dirty = np.zeros_like(latest)
        t_ps = np.array([[1.0, 10.0], [1.0, 10.0]])
        fast_id = np.array([[5, -1]])     # shard 0
        slow_id = np.array([[25, -1]])    # shard 1
        Cf = cost_matrix_sparse_ps(part.to_linear(fast_id), latest, dirty,
                                   t_ps, part, linear=True)
        Cs = cost_matrix_sparse_ps(part.to_linear(slow_id), latest, dirty,
                                   t_ps, part, linear=True)
        np.testing.assert_allclose(Cf, [[1.0, 1.0]])
        np.testing.assert_allclose(Cs, [[10.0, 10.0]])


class TestPsStateUpdate:
    _step = staticmethod(jax.jit(esd_state_update_sparse,
                                 static_argnums=(2, 3)))

    def _trace(self, part, capacity, iters=15, n=3, L=6, seed=9):
        Vs = part.linear_size
        s_plain = esd_sparse_init(n, Vs, capacity, L)
        s_ps = esd_sparse_init(n, Vs, capacity, L)
        r = np.random.default_rng(seed)
        for it in range(iters):
            ids_list = np.full((n, L), -1, np.int32)
            for j in range(n):
                g = np.sort(r.choice(part.vocab, r.integers(0, L + 1),
                                     replace=False))
                lin = np.sort(np.asarray(part.to_linear(g)))
                ids_list[j, :len(lin)] = lin
            s_plain, c0 = self._step(s_plain, jnp.asarray(ids_list),
                                     capacity, None)
            s_ps, c1 = self._step(s_ps, jnp.asarray(ids_list), capacity, part)
            # state transition and per-worker counts are untouched by part
            for f in ("latest", "dirty", "last_access", "slots"):
                np.testing.assert_array_equal(
                    np.asarray(getattr(s_plain, f)),
                    np.asarray(getattr(s_ps, f)), err_msg=f"it{it} {f}")
            for key in c0:
                np.testing.assert_array_equal(np.asarray(c0[key]),
                                              np.asarray(c1[key]),
                                              err_msg=f"it{it} {key}")
            # the ps breakdown sums back to the per-worker counts
            for op in ("miss_pull", "update_push", "evict_push"):
                ps = np.asarray(c1[op + "_ps"])
                assert ps.shape == (n, part.n_ps)
                np.testing.assert_array_equal(ps.sum(axis=1),
                                              np.asarray(c1[op]),
                                              err_msg=f"it{it} {op}_ps")

    @pytest.mark.parametrize("layout", ["contiguous", "hashed"])
    def test_counts_and_state(self, layout):
        part = make_partition(50, 3, layout)
        self._trace(part, capacity=None)
        self._trace(part, capacity=8)

    def test_nps1_partition_is_inert(self):
        self._trace(PsPartition.identity(40), capacity=6)

    def test_plane_width_mismatch_raises(self):
        part = make_partition(40, 3)
        state = esd_sparse_init(2, 40)       # 40 != part.linear_size (42)
        with pytest.raises(ValueError):
            esd_state_update_sparse(state, jnp.zeros((2, 4), jnp.int32),
                                    None, part)


class TestNeedIdsLocal:
    def test_projects_to_owned_rows(self):
        part = make_partition(30, 3)          # 10 rows per shard
        need = jnp.asarray(np.array([[0, 10, 25, -1],
                                     [9, 11, -1, -1]], np.int32))
        lin = part.to_linear(need)
        per_ps = np.asarray(need_ids_local(lin, part))
        assert per_ps.shape == (3, 2, 4)
        # worker 0: local row 0 on PS0, 0 on PS1, 5 on PS2
        np.testing.assert_array_equal(per_ps[0, 0], [0, -1, -1, -1])
        np.testing.assert_array_equal(per_ps[1, 0], [0, -1, -1, -1])
        np.testing.assert_array_equal(per_ps[2, 0], [5, -1, -1, -1])
        # worker 1: rows 9 on PS0 and 1 on PS1; nothing on PS2
        np.testing.assert_array_equal(per_ps[0, 1], [9, -1, -1, -1])
        np.testing.assert_array_equal(per_ps[1, 1], [1, -1, -1, -1])
        np.testing.assert_array_equal(per_ps[2, 1], [-1, -1, -1, -1])
        # round-trip: every (shard, local) maps back to the original ids
        for p in range(3):
            for j in range(2):
                loc = per_ps[p, j][per_ps[p, j] >= 0]
                back = part.local_to_global(np.full_like(loc, p), loc)
                orig = np.asarray(need[j])
                orig = orig[orig >= 0]
                assert set(back.tolist()) <= set(orig.tolist())


class TestPsClusterCache:
    @pytest.mark.parametrize("layout", ["contiguous", "hashed"])
    def test_dense_sparse_ps_counts_identical(self, layout):
        vocab, n, cap = 60, 3, 8
        part = make_partition(vocab, 3, layout)
        Vs = part.linear_size
        dense = ClusterCache(n, Vs, cap, policy="lru", part=part)
        sparse = SparseClusterCache(n, Vs, cap, policy="lru", part=part)
        r = np.random.default_rng(11)
        for it in range(20):
            batches = [np.asarray(part.to_linear(
                r.choice(vocab, r.integers(0, 7), replace=False)))
                for _ in range(n)]
            sd, ss = dense.step(batches), sparse.step(batches)
            for f in ("miss_pull_ps", "update_push_ps", "evict_push_ps"):
                np.testing.assert_array_equal(getattr(sd, f), getattr(ss, f),
                                              err_msg=f"it{it} {f}")
                np.testing.assert_array_equal(
                    getattr(sd, f).sum(axis=1),
                    getattr(sd, f.removesuffix("_ps")),
                    err_msg=f"it{it} {f} row-sum")

    def test_vocab_mismatch_raises(self):
        part = make_partition(40, 3)
        with pytest.raises(ValueError):
            ClusterCache(2, 40, 5, part=part)     # 40 != linear_size 42


class TestPsSimulator:
    _base = dict(workload=WORKLOADS["tiny"], n_workers=4, batch_per_worker=8,
                 iters=8, warmup=2)

    def test_nps1_ps_path_bitwise_equals_plain(self):
        plain = simulate(SimConfig(**self._base))
        bw = np.array([5.0, 5.0, 0.5, 0.5]) * 1e9 / 8
        ps = simulate(SimConfig(**self._base, n_ps=1,
                                ps_bandwidths=bw[:, None]))
        assert (plain.per_iter_cost == ps.per_iter_cost).all()
        assert (plain.per_iter_time == ps.per_iter_time).all()
        assert plain.hit_ratio == ps.hit_ratio

    @pytest.mark.parametrize("layout", ["contiguous", "hashed"])
    def test_hetero_ps_esd_beats_random(self, layout):
        hb = hetero_ps_bandwidths(4, 2)
        esd = simulate(SimConfig(**self._base, n_ps=2, ps_layout=layout,
                                 ps_bandwidths=hb))
        rnd = simulate(SimConfig(**self._base, n_ps=2, ps_layout=layout,
                                 ps_bandwidths=hb, mechanism="random"))
        assert esd.cost < rnd.cost

    def test_engines_identical_under_ps(self):
        hb = hetero_ps_bandwidths(4, 2)
        cfg = SimConfig(**self._base, n_ps=2, ps_bandwidths=hb)
        rs = simulate(cfg)
        rd = simulate(dataclasses.replace(cfg, engine="dense"))
        assert (rs.per_iter_cost == rd.per_iter_cost).all()
        assert rs.hit_ratio == rd.hit_ratio

    def test_formerly_unsupported_mechanisms_run(self):
        """FAE / stale-HET used to raise under n_ps > 1; they now carry
        per-PS accounting (see TestBaselineMultiPs for the breakdowns)."""
        r = simulate(SimConfig(**self._base, n_ps=2, mechanism="fae"))
        assert np.isfinite(r.cost)
        r = simulate(SimConfig(**self._base, n_ps=2, mechanism="het",
                               het_staleness=2))
        assert np.isfinite(r.cost)


class TestPsModelAndSharding:
    def test_ps_stacked_table_loss_equivalent(self):
        """PS-stacking permutes table rows in lockstep with the id
        translation, so the forward pass is exactly invariant."""
        from repro.configs import DLRM_CONFIGS
        from repro.models import dlrm

        cfg = DLRM_CONFIGS["wdl-tiny"]
        wl = WORKLOADS[cfg.workload]
        part = make_partition(wl.vocab, 3, "hashed")
        params = dlrm.init_params(jax.random.key(0), cfg, wl)
        stacked = dlrm.ps_stack_tables(params, part)
        assert stacked["embed"].shape == (3, part.max_rows,
                                          cfg.embedding_dim)
        rng = np.random.default_rng(2)
        sparse = wl.sample_batch(rng, 8)
        dense = wl.dense_batch(rng, 8)
        flat = dlrm.forward(params, cfg, jnp.asarray(sparse),
                            jnp.asarray(dense))
        lin = part.to_linear(sparse)
        ps = dlrm.forward(stacked, cfg, jnp.asarray(lin), jnp.asarray(dense))
        np.testing.assert_allclose(np.asarray(ps), np.asarray(flat),
                                   rtol=1e-6)

    def test_rowwise_adagrad_ps_stack_accumulators(self):
        from repro.optim import get_optimizer

        opt = get_optimizer("rowwise_adagrad", 0.1)
        params = {"embed": jnp.ones((2, 5, 4)), "mlp": jnp.ones((3, 4)),
                  "b": jnp.ones((4,))}
        state = opt.init(params)
        assert state["embed"].shape == (2, 5)      # per (shard, local_row)
        assert state["mlp"].shape == (3,)
        assert state["b"].shape == (4,)
        grads = jax.tree.map(jnp.ones_like, params)
        new, state2 = opt.update(grads, state, params)
        assert state2["embed"].shape == (2, 5)
        assert np.isfinite(np.asarray(new["embed"])).all()

    def test_param_specs_ps_stacked_placement(self):
        from jax.sharding import PartitionSpec as P

        from repro.dist.sharding import param_specs

        # n_ps divides the (mocked) data axis -> PS axis sharded
        tree = {"embed": jax.ShapeDtypeStruct((4, 25, 8), jnp.float32),
                "wide": jax.ShapeDtypeStruct((4, 25, 1), jnp.float32),
                "top": [{"w": jax.ShapeDtypeStruct((8, 1), jnp.float32)}]}
        mesh = make_mesh((1, 1), ("data", "model"))
        specs = param_specs(tree, mesh=mesh)
        assert specs["embed"] == P("data", None, None)
        assert specs["wide"] == P("data", None, None)
        assert specs["top"][0]["w"] == P(None, None)

    def test_train_driver_multips_smoke(self):
        """2 PS shards end-to-end through the jitted train step."""
        from repro.launch.train import main

        metrics = main(["--arch", "wdl-tiny", "--steps", "2",
                        "--batch-per-worker", "8", "--esd-alpha", "0",
                        "--n-ps", "2", "--ps-hetero"])
        assert len(metrics) == 2
        assert all(np.isfinite(m["loss"]) for m in metrics)
        assert metrics[0]["cost"] > 0


class TestPerPsCapacity:
    """Per-PS worker cache budgets (capacity_ps) in both sparse engines."""

    def _ids_batch(self, rng, part, n, L):
        ids = np.full((n, L), -1, np.int32)
        for j in range(n):
            u = np.unique(part.to_linear(rng.integers(0, part.vocab, L)))
            ids[j, :len(u)] = u
        return ids

    def test_state_update_seq_len1_bitwise_int(self, rng):
        """capacity=[c] at n_ps=1 is bitwise the plain-int path."""
        n, V, L, cap = 3, 64, 8, 10
        part = make_partition(V, 1)
        s_int = esd_sparse_init(n, V, cap, max_ids=L)
        s_seq = esd_sparse_init(n, V, [cap], max_ids=L)
        for _ in range(6):
            ids = jnp.asarray(self._ids_batch(rng, part, n, L))
            s_int, c_int = esd_state_update_sparse(s_int, ids, cap, part)
            s_seq, c_seq = esd_state_update_sparse(s_seq, ids, [cap], part)
            for key in ("miss_pull", "update_push", "evict_push"):
                np.testing.assert_array_equal(np.asarray(c_int[key]),
                                              np.asarray(c_seq[key]))
        np.testing.assert_array_equal(np.asarray(s_int.latest),
                                      np.asarray(s_seq.latest))
        np.testing.assert_array_equal(np.asarray(s_int.dirty),
                                      np.asarray(s_seq.dirty))
        np.testing.assert_array_equal(np.sort(np.asarray(s_int.slots)),
                                      np.sort(np.asarray(s_seq.slots)))

    def test_state_update_budgets_respected(self, rng):
        n, V, L = 3, 64, 8
        caps = [6, 3]
        part = make_partition(V, 2)
        s = esd_sparse_init(n, part.linear_size, caps, max_ids=L)
        for _ in range(10):
            ids = jnp.asarray(self._ids_batch(rng, part, n, L))
            s, c = esd_state_update_sparse(s, ids, caps, part)
        lat = np.asarray(s.latest)
        need = np.asarray(ids)
        for j in range(n):
            res = np.where(lat[j])[0]
            cnt = np.bincount(np.asarray(part.shard_of_linear(res)),
                              minlength=2)
            pinned = need[j][need[j] >= 0]
            pin_cnt = np.bincount(np.asarray(part.shard_of_linear(pinned)),
                                  minlength=2)
            # budget + this step's pinned ids bound the resident set
            assert (cnt <= np.asarray(caps) + pin_cnt).all(), (cnt, pin_cnt)
        np.testing.assert_array_equal(
            np.asarray(c["evict_push_ps"]).sum(axis=1),
            np.asarray(c["evict_push"]))

    def test_state_update_seq_errors(self, rng):
        n, V, L = 2, 32, 4
        part = make_partition(V, 2)
        s = esd_sparse_init(n, part.linear_size, [4, 4], max_ids=L)
        ids = jnp.asarray(self._ids_batch(rng, part, n, L))
        with pytest.raises(ValueError, match="part"):
            esd_state_update_sparse(s, ids, [4, 4])        # no part
        with pytest.raises(ValueError, match="entries"):
            esd_state_update_sparse(s, ids, [4, 4, 4], part)
        small = esd_sparse_init(n, part.linear_size, [2, 2], max_ids=L)
        with pytest.raises(ValueError, match="slot buffer"):
            esd_state_update_sparse(small, ids, [4, 4], part)

    def test_cluster_cache_budgets(self, rng):
        n, V = 3, 80
        part = make_partition(V, 2)
        caps = [10, 7]
        c = SparseClusterCache(n, part.linear_size, caps, policy="lru",
                               part=part)
        for _ in range(12):
            batches = [np.unique(part.to_linear(
                rng.integers(0, V, 7))) for _ in range(n)]
            st = c.step(batches)
        for j in range(n):
            res = np.where(c.present[j])[0]
            cnt = np.bincount(np.asarray(part.shard_of_linear(res)),
                              minlength=2)
            assert (cnt <= np.asarray(caps)).all(), cnt
        np.testing.assert_array_equal(st.evict_push_ps.sum(axis=1),
                                      st.evict_push)
        # prefill respects per-shard budgets
        hot = part.to_linear(np.argsort(rng.random(V)))
        c.prefill(hot)
        for j in range(n):
            cnt = np.bincount(np.asarray(part.shard_of_linear(
                np.where(c.present[j])[0])), minlength=2)
            assert (cnt <= np.asarray(caps)).all()

    def test_cluster_cache_rejects(self):
        part = make_partition(40, 2)
        with pytest.raises(ValueError, match="Sparse"):
            ClusterCache(2, part.linear_size, [5, 5], part=part)
        with pytest.raises(ValueError, match="n_ps"):
            SparseClusterCache(2, part.linear_size, [5, 5, 5], part=part)
        with pytest.raises(ValueError, match="n_ps"):
            SparseClusterCache(2, 40, [5, 5])              # no part


class TestBaselineMultiPs:
    """FAE / stale-HET per-PS accounting (SimConfig no longer rejects)."""

    @pytest.mark.parametrize("mech,kw", [("fae", {}),
                                         ("het", {"het_staleness": 2})])
    def test_simulator_accepts(self, mech, kw):
        cfg = SimConfig(workload=WORKLOADS["tiny"], n_workers=4,
                        batch_per_worker=8, iters=6, warmup=2,
                        mechanism=mech, n_ps=2,
                        ps_bandwidths=hetero_ps_bandwidths(4, 2), **kw)
        r = simulate(cfg)
        assert np.isfinite(r.cost) and r.cost > 0

    @pytest.mark.parametrize("mech,kw", [("fae", {}),
                                         ("het", {"het_staleness": 2})])
    def test_ps_rows_sum_to_totals(self, mech, kw, rng):
        from repro.core.baselines import FAECache, HETCache

        V = 60
        part = make_partition(V, 3)
        if mech == "fae":
            hot = part.to_linear(np.argsort(rng.random(V)))
            cache = FAECache(3, part.linear_size, 20, hot, part=part)
        else:
            cache = HETCache(3, part.linear_size, 20, policy="lru",
                             staleness=kw["het_staleness"], part=part)
        for _ in range(5):
            batches = [np.unique(part.to_linear(
                rng.integers(0, V, 10))) for _ in range(3)]
            st = cache.step(batches)
            for op in ("miss_pull", "update_push", "evict_push"):
                np.testing.assert_array_equal(
                    getattr(st, op + "_ps").sum(axis=1), getattr(st, op))
