"""repro.exchange: plan compilation, ragged executor, and their wiring.

Contracts under test:
  * plan round-trip (property-tested over random assignments including
    fully-skewed and empty destinations): compile -> pack -> (emulated)
    all_to_all -> compact reproduces direct indexing exactly;
  * bitwise padded-vs-ragged equivalence on uniform assignments (budget
    = m/n, every mask full) and for n = 1, in the real shard_map path;
  * plan invariants: counts/offsets/buckets consistency, pow2 buckets,
    byte accounting identities, pad reduction under skew;
  * esd_dispatch(cap_slack) lowers the Alg.-1 objective vs the hard cap
    and the simulator's ragged accounting never ships more than padded;
  * the Pallas pack kernel matches the jnp packer bitwise;
  * use_pallas with n_ps > 1 degrades to the jnp ps cost matrix with a
    one-time RuntimeWarning (pinned — it used to raise).
"""
import sys
import warnings
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, "tests")
from hypothesis import given, settings, strategies as st

from repro.core import SimConfig, simulate
from repro.core.dispatch_tpu import (
    dispatch_cap,
    esd_dispatch,
    esd_sparse_init,
    exchange_budget,
    hybrid_dispatch_jax,
)
from repro.data.synthetic import WORKLOADS
from repro.launch.mesh import make_mesh
from repro.exchange import (
    bucket_sizes,
    compact_recv,
    compile_plan,
    gather_reference,
    pack_send,
)
from repro.kernels.exchange_pack import gather_rows_pallas


def _emulated_exchange(samples, assign, n, budget, use_pallas=False):
    """Run the executor's pack/compact per shard with the collective
    emulated in numpy (all_to_all: recv block i on dst j == send block j
    on src i) — the exact dataflow of the shard_map path."""
    k, = assign.shape
    m = k // n
    sends, counts = [], []
    for i in range(n):
        s, c, _ = pack_send(jnp.asarray(samples[i * m:(i + 1) * m]),
                            jnp.asarray(assign[i * m:(i + 1) * m]),
                            n, budget, use_pallas=use_pallas)
        sends.append(np.asarray(s))
        counts.append(np.asarray(c))
    sends, counts = np.stack(sends), np.stack(counts)
    outs, totals = [], []
    for j in range(n):
        out, total = compact_recv(jnp.asarray(sends[:, j]),
                                  jnp.asarray(counts[:, j]), n * budget)
        outs.append(np.asarray(out))
        totals.append(int(total))
    return outs, totals


class TestPlan:
    @settings(max_examples=25, deadline=None)
    @given(st.integers(1, 6), st.integers(1, 32), st.integers(0, 3),
           st.integers(0, 2 ** 31 - 1))
    def test_round_trip_random(self, n, m, skew_mode, seed):
        rng = np.random.default_rng(seed)
        k = n * m
        samples = rng.integers(0, 997, (k, 3)).astype(np.int32)
        if skew_mode == 1:          # fully skewed: everything to worker 0
            assign = np.zeros(k, np.int64)
        elif skew_mode == 2:        # empty destinations: only worker n-1
            assign = np.full(k, n - 1, np.int64)
        elif skew_mode == 3 and n > 1:  # half the workers never receive
            assign = rng.integers(0, (n + 1) // 2, k)
        else:
            assign = rng.integers(0, n, k)
        plan = compile_plan(assign, n, row_bytes=3 * 4)

        # plan invariants
        np.testing.assert_array_equal(plan.counts.sum(axis=1), m)
        np.testing.assert_array_equal(plan.offsets[:, -1], m)
        np.testing.assert_array_equal(
            np.diff(plan.offsets, axis=1), plan.counts)
        nz = plan.counts > 0
        assert (plan.buckets >= plan.counts).all()
        assert (plan.buckets[nz] < 2 * plan.counts[nz]).all()  # pow2 < 2x
        assert (plan.buckets[~nz] == 0).all()
        assert plan.stats.payload_bytes == k * 3 * 4
        assert plan.stats.ragged_bytes <= plan.stats.padded_bytes

        # execute (emulated collective) and compare against the oracle
        outs, totals = _emulated_exchange(samples, assign, n, plan.budget)
        ref = gather_reference(samples, assign, n)
        for j in range(n):
            assert totals[j] == len(ref[j])
            np.testing.assert_array_equal(outs[j][:totals[j]], ref[j])
            assert (outs[j][totals[j]:] == -1).all()

    def test_bucket_sizes(self):
        np.testing.assert_array_equal(
            bucket_sizes(np.array([0, 1, 2, 3, 5, 8, 9])),
            np.array([0, 1, 2, 4, 8, 8, 16]))
        np.testing.assert_array_equal(
            bucket_sizes(np.array([9]), cap=12), np.array([12]))
        with pytest.raises(ValueError):
            bucket_sizes(np.array([5]), cap=4)

    def test_bucket_cap_clamp_non_pow2(self):
        """Regression: a non-pow2 cap used to replace EVERY bucket above
        the largest pow2 <= cap with the raw count, leaking one distinct
        block shape per count; now cap itself is the single terminal
        bucket."""
        out = bucket_sizes(np.array([70, 3, 0, 96]), cap=96)
        np.testing.assert_array_equal(out, np.array([96, 4, 0, 96]))
        for b in out[out > 0]:
            assert b == 96 or (b & (b - 1)) == 0

    def test_schedule_len_bound(self):
        """len(schedule) <= floor(log2(cap)) + 2: all pow2s up to cap
        plus the terminal bucket (cap, or the fixed-shape block when that
        is smaller)."""
        rng = np.random.default_rng(0)
        for cap in (7, 8, 96, 100):
            n, m = 8, cap
            assign = rng.integers(0, n, n * m)
            plan = compile_plan(assign, n, cap=cap)
            assert len(plan.schedule) <= int(np.floor(np.log2(cap))) + 2
            top = min(cap, plan.padded_block)
            for b in plan.schedule:
                assert b == top or (b & (b - 1)) == 0

    def test_skew_pad_reduction(self):
        """Fully skewed: ragged ships zero pad, padded ships ~n x."""
        n, m = 8, 32
        plan = compile_plan(np.zeros(n * m, np.int64), n)
        assert plan.stats.pad_bytes_ragged == 0
        assert plan.stats.pad_reduction == 1.0
        assert plan.padded_block == m

    def test_uniform_no_pad_either_way(self):
        n, m = 4, 16
        assign = np.tile(np.arange(n), m)          # m/n everywhere
        plan = compile_plan(assign, n)
        assert plan.stats.pad_bytes_ragged == 0
        assert plan.stats.pad_bytes_padded == 0
        assert plan.schedule == (m // n,)
        # regression: both-zero pad is the BEST case and reports 1.0
        # (it used to report 0.0, the worst score)
        assert plan.stats.pad_reduction == 1.0

    def test_elastic_padded_baseline_counts_active_sources(self):
        """Regression: with an elastic membership mask the fixed-shape
        baseline used to charge all n sources, but dead sources hold no
        samples — padded_bytes is n_active^2 * block * row_bytes."""
        n, m = 4, 9
        active = np.array([True, True, False, True])
        rng = np.random.default_rng(3)
        live = np.flatnonzero(active)
        assign = live[rng.integers(0, live.size, n * m)]
        plan = compile_plan(assign, n, active=active)
        block = plan.padded_block
        assert plan.stats.padded_bytes == 3 * 3 * block * 4
        # inactive destination is a hard error
        bad = assign.copy()
        bad[0] = 2
        with pytest.raises(ValueError):
            compile_plan(bad, n, active=active)

    def test_codec_tagged_plan(self):
        """int8 plan: payload is exactly 4x smaller than fp32, scale/zp
        travel in meta_bytes, never in the pad accounting."""
        n, m, E = 4, 16, 32
        rng = np.random.default_rng(7)
        assign = rng.integers(0, n, n * m)
        plain = compile_plan(assign, n, row_bytes=4 * E)
        quant = compile_plan(assign, n, codec="int8", row_elems=E)
        assert quant.stats.codec == "int8"
        assert quant.stats.byte_reduction == 4.0
        assert quant.stats.payload_bytes * 4 == plain.stats.payload_bytes
        assert quant.stats.payload_fp32_bytes == plain.stats.payload_bytes
        assert quant.stats.meta_bytes > 0
        s = quant.stats.summary()
        assert s["codec"] == "int8" and s["byte_reduction"] == 4.0
        # plain plans carry no codec keys
        assert "codec" not in plain.stats.summary()
        with pytest.raises(ValueError):
            compile_plan(assign, n, codec="int8")  # row_elems missing

    def test_bad_inputs(self):
        with pytest.raises(ValueError):
            compile_plan(np.zeros(7, np.int64), 2)      # k not divisible
        with pytest.raises(ValueError):
            compile_plan(np.array([0, 2]), 2, m=1)      # target out of range


class TestRaggedExecutor:
    def test_uniform_bitwise_equals_padded(self, rng):
        """budget = m/n + full masks: every stage is the identity of the
        padded path's pack/reshape."""
        n, m, F = 4, 16, 3
        k = n * m
        samples = rng.integers(0, 100, (k, F)).astype(np.int32)
        assign = np.tile(np.arange(n), (n, m // n)).reshape(-1)
        outs, totals = _emulated_exchange(samples, assign, n, m // n)
        # padded path per shard: sort-by-assign, reshape, exchange
        for j in range(n):
            blocks = []
            for i in range(n):
                loc = samples[i * m:(i + 1) * m]
                a = assign[i * m:(i + 1) * m]
                order = np.argsort(a, kind="stable")
                blocks.append(loc[order].reshape(n, m // n, F)[j])
            padded = np.concatenate(blocks)
            assert totals[j] == m
            np.testing.assert_array_equal(outs[j][:m], padded)

    def test_n1_shard_map_bitwise(self, rng):
        """n = 1 real shard_map: ragged esd_dispatch == padded bitwise."""
        from jax import shard_map
        from jax.sharding import PartitionSpec as P

        m, F, V = 8, 3, 50
        mesh = make_mesh((1,), ("data",))
        samples = jnp.asarray(rng.integers(0, V, (m, F)), jnp.int32)
        state = esd_sparse_init(1, V)
        t = jnp.ones((1,), jnp.float32)

        def run(mode):
            def f(s):
                out, assign = esd_dispatch(s, state, t, alpha=0.0,
                                           exchange=mode)
                return out, assign
            return shard_map(f, mesh=mesh, in_specs=(P("data", None),),
                             out_specs=(P("data", None), P("data")),
                             check_vma=False)(samples)

        out_p, a_p = run("padded")
        out_r, a_r = run("ragged")
        np.testing.assert_array_equal(np.asarray(out_p), np.asarray(out_r))
        np.testing.assert_array_equal(np.asarray(a_p), np.asarray(a_r))

    def test_pallas_pack_matches_jnp(self, rng):
        n, m, F, budget = 4, 24, 5, 8
        rows = jnp.asarray(rng.integers(0, 100, (m, F)), jnp.int32)
        assign = jnp.asarray(rng.integers(0, n, (m,)), jnp.int32)
        s_j, c_j, o_j = pack_send(rows, assign, n, budget)
        s_p, c_p, o_p = pack_send(rows, assign, n, budget, use_pallas=True)
        np.testing.assert_array_equal(np.asarray(s_p), np.asarray(s_j))
        np.testing.assert_array_equal(np.asarray(c_p), np.asarray(c_j))
        assert int(o_j) == int(o_p)

    def test_pallas_pack_drops_overflow_like_jnp(self):
        """Rows beyond a destination's budget are dropped, not routed
        into the next destination's block (regression: the flat slot
        index used to spill across block boundaries)."""
        n, budget = 3, 2
        rows = jnp.arange(12, dtype=jnp.int32).reshape(6, 2)
        assign = jnp.asarray([0, 0, 1, 0, 2, 2], jnp.int32)  # dst 0 overflows
        s_j, c_j, o_j = pack_send(rows, assign, n, budget)
        s_p, c_p, o_p = pack_send(rows, assign, n, budget, use_pallas=True)
        np.testing.assert_array_equal(np.asarray(s_p), np.asarray(s_j))
        np.testing.assert_array_equal(np.asarray(c_p), np.asarray(c_j))
        # the dropped third dst-0 row is counted, both paths
        assert int(o_j) == 1 and int(o_p) == 1

    def test_pack_send_overflow_count(self):
        n, budget = 4, 2
        rows = jnp.arange(16, dtype=jnp.int32).reshape(8, 2)
        assign = jnp.zeros((8,), jnp.int32)      # all 8 rows -> dst 0
        _, counts, ov = pack_send(rows, assign, n, budget)
        assert int(ov) == 6                      # 8 rows, 2 fit
        assert int(counts[0]) == 8               # counts report intent

    def test_raise_on_overflow(self):
        from repro.launch.steps import raise_on_overflow

        raise_on_overflow({})                                    # no counter
        raise_on_overflow({"exchange_overflow": jnp.zeros((), jnp.int32)})
        with pytest.raises(RuntimeError, match="dropped 3 rows"):
            raise_on_overflow({"exchange_overflow": jnp.asarray(3)})

    def test_gather_rows_pallas(self, rng):
        rows = jnp.asarray(rng.integers(0, 9, (6, 4)), jnp.int32)
        idx = jnp.asarray([3, -1, 0, 5, -1], jnp.int32)
        out = np.asarray(gather_rows_pallas(rows, idx))
        want = np.where((np.asarray(idx) >= 0)[:, None],
                        np.asarray(rows)[np.maximum(np.asarray(idx), 0)], -1)
        np.testing.assert_array_equal(out, want)


class TestCapSlack:
    def test_dispatch_cap_and_budget(self):
        assert dispatch_cap(64, 8) == 8
        assert dispatch_cap(64, 8, 0.5) == 12
        assert dispatch_cap(64, 8, 100.0) == 64
        assert exchange_budget(8, 64) == 8
        assert exchange_budget(12, 64) == 16
        assert exchange_budget(65, 64) == 64

    def test_slack_lowers_cost(self, rng):
        """On a skewed cost matrix the relaxed cap strictly lowers the
        realized Alg.-1 objective of the greedy assignment."""
        m, n = 64, 8
        C = jnp.asarray(rng.random((m, n)), jnp.float32)
        C = C.at[:, 0].mul(0.05)          # worker 0 is far cheaper
        a_hard = np.asarray(hybrid_dispatch_jax(C, m, 0.0))
        a_slack = np.asarray(hybrid_dispatch_jax(C, m, 0.0,
                                                 cap=dispatch_cap(m, n, 1.0)))
        Cn = np.asarray(C)
        cost_hard = Cn[np.arange(m), a_hard].sum()
        cost_slack = Cn[np.arange(m), a_slack].sum()
        assert cost_slack < cost_hard
        assert np.bincount(a_hard, minlength=n).max() <= m // n
        assert np.bincount(a_slack, minlength=n).max() > m // n

    def test_padded_rejects_slack(self, rng):
        samples = jnp.asarray(rng.integers(0, 20, (8, 2)), jnp.int32)
        state = esd_sparse_init(1, 20)
        with pytest.raises(ValueError, match="cap_slack"):
            esd_dispatch(samples, state, jnp.ones((1,)), 0.0,
                         cap_slack=0.5, exchange="padded")

    def test_simulator_slack_and_bytes(self):
        base = dict(workload=WORKLOADS["tiny"], n_workers=4,
                    batch_per_worker=16, iters=8, warmup=2,
                    mechanism="esd", alpha=0.0)
        rp = simulate(SimConfig(exchange="padded", **base))
        rr = simulate(SimConfig(exchange="ragged", **base))
        rs = simulate(SimConfig(exchange="ragged", cap_slack=0.5, **base))
        # identical dispatch => identical payload; ragged never ships more
        assert rr.exchange["payload_bytes"] == rp.exchange["payload_bytes"]
        assert rr.exchange["wire_bytes"] <= rp.exchange["wire_bytes"]
        # the relaxed cap strictly lowers the Alg.-1 objective
        assert rs.alg1_cost < rr.alg1_cost
        # without slack the cache-protocol cost is untouched by exchange
        r0 = simulate(SimConfig(**base))
        assert r0.exchange is None
        assert rp.cost == r0.cost
        with pytest.raises(ValueError, match="cap_slack"):
            simulate(SimConfig(cap_slack=0.5, **base))


class TestPallasPsDegrade:
    def test_warns_once_and_matches_jnp(self, rng):
        """use_pallas + n_ps > 1: no longer raises — one RuntimeWarning,
        then the jnp ps cost matrix result."""
        import repro.core.dispatch_tpu as dt
        from jax import shard_map
        from jax.sharding import PartitionSpec as P
        from repro.ps import make_partition

        V, m, F = 40, 8, 3
        part = make_partition(V, 2)
        mesh = make_mesh((1,), ("data",))
        samples = jnp.asarray(
            part.to_linear(rng.integers(0, V, (m, F))), jnp.int32)
        state = esd_sparse_init(1, part.linear_size)
        t = jnp.ones((1, 2), jnp.float32)

        def run(use_pallas):
            def f(s):
                return esd_dispatch(s, state, t, alpha=0.0, part=part,
                                    use_pallas=use_pallas)
            return shard_map(f, mesh=mesh, in_specs=(P("data", None),),
                             out_specs=(P("data", None), P("data")),
                             check_vma=False)(samples)

        dt._pallas_ps_warned = False
        with warnings.catch_warnings(record=True) as w:
            warnings.simplefilter("always")
            out_p, a_p = run(use_pallas=True)
            ours = [x for x in w if "Pallas" in str(x.message)]
            assert len(ours) == 1
            assert issubclass(ours[0].category, RuntimeWarning)
        with warnings.catch_warnings(record=True) as w:
            warnings.simplefilter("always")
            run(use_pallas=True)               # second call: silent
            assert not [x for x in w if "Pallas" in str(x.message)]
        out_j, a_j = run(use_pallas=False)
        np.testing.assert_array_equal(np.asarray(out_p), np.asarray(out_j))
        np.testing.assert_array_equal(np.asarray(a_p), np.asarray(a_j))


MULTIDEV_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from jax import shard_map
from repro.core.dispatch_tpu import esd_dispatch, esd_sparse_init, \
    dispatch_cap, exchange_budget
from repro.exchange import gather_reference
from repro.exchange.ragged import ragged_exchange
from repro.launch.mesh import make_mesh

n, m, F, V = 8, 16, 4, 100
mesh = make_mesh((n,), ("data",))
rng = np.random.default_rng(0)
samples = rng.integers(0, V, (n * m, F)).astype(np.int32)
state = esd_sparse_init(n, V)
t = jnp.asarray(np.where(np.arange(n) < 4, 1.0, 10.0), jnp.float32)

def run(mode, cap_slack=0.0):
    def f(s):
        return esd_dispatch(s, state, t, alpha=0.0, exchange=mode,
                            cap_slack=cap_slack)
    out_rows = (m if cap_slack == 0.0
                else n * exchange_budget(dispatch_cap(m, n, cap_slack), m))
    return shard_map(f, mesh=mesh, in_specs=(P("data", None),),
                     out_specs=(P("data", None), P("data")),
                     check_vma=False)(jnp.asarray(samples))

# 1) hard cap: ragged is bitwise-equal to padded on the real collective
out_p, a_p = run("padded")
out_r, a_r = run("ragged")
assert np.array_equal(np.asarray(a_p), np.asarray(a_r))
assert np.array_equal(np.asarray(out_p), np.asarray(out_r)), "ragged != padded"

# 2) cap_slack: skewed assignment round-trips through the real collective
out_s, a_s = run("ragged", cap_slack=1.0)
out_s, a_s = np.asarray(out_s), np.asarray(a_s)
counts = np.bincount(a_s, minlength=n)
ref = gather_reference(samples, a_s, n)
B = exchange_budget(dispatch_cap(m, n, 1.0), m)
for j in range(n):
    blk = out_s[j * n * B:(j + 1) * n * B]
    valid = blk[(blk != -1).any(axis=1)]
    assert len(valid) == len(ref[j]), (j, len(valid), len(ref[j]))
    assert np.array_equal(valid, ref[j]), f"worker {j} payload mismatch"
orig = sorted(map(tuple, samples.tolist()))
got = sorted(map(tuple, out_s[(out_s != -1).any(axis=1)].tolist()))
assert orig == got, "exchange lost/duplicated samples"

# 3) raw ragged_exchange with an adversarial assignment (empty dsts)
skew = np.zeros(n * m, np.int64)
def g(s, a):
    out, total, rc, _ = ragged_exchange(s, a, "data", m, out_rows=n * m)
    return out, total[None], rc[None]
out_k, tot, rc = shard_map(
    g, mesh=mesh, in_specs=(P("data", None), P("data")),
    out_specs=(P("data", None), P("data"), P("data", None)),
    check_vma=False)(jnp.asarray(samples), jnp.asarray(skew))
tot = np.asarray(tot)
assert tot[0] == n * m and (tot[1:] == 0).all(), tot
np.testing.assert_array_equal(
    np.asarray(out_k)[:n * m], gather_reference(samples, skew, n)[0])
print("MULTIDEV_EXCHANGE_OK")
"""


@pytest.mark.slow
def test_shard_map_ragged_8dev():
    import os
    import subprocess

    res = subprocess.run(
        [sys.executable, "-c", MULTIDEV_SCRIPT],
        capture_output=True, text=True, timeout=600,
        env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
             "HOME": os.environ.get("HOME", ""),
             "JAX_PLATFORMS": os.environ.get("JAX_PLATFORMS", "cpu")},
        cwd=Path(__file__).resolve().parents[1],
    )
    assert "MULTIDEV_EXCHANGE_OK" in res.stdout, res.stdout + res.stderr


class TestExchangeSpecs:
    def test_specs_shapes(self):
        from repro.dist.sharding import exchange_specs

        mesh = make_mesh((1, 1), ("data", "model"))
        specs = exchange_specs(mesh)
        assert len(specs["send"]) == 4 and specs["send"][0] is not None
        assert len(specs["counts"]) == 2
        # placeable on a real mesh
        from repro.dist.sharding import to_shardings
        to_shardings(specs, mesh)
