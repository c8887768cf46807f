"""ESD-on-TPU layer: jittable dispatchers + shard_map exchange + in-jit
cache protocol.  Multi-device cases run in a subprocess with
XLA_FLAGS=--xla_force_host_platform_device_count=8 (tests themselves must
keep the default single device)."""
import os
import subprocess
from pathlib import Path
import sys
import textwrap

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import ClusterCache, heu_dispatch
from repro.core.dispatch_tpu import (
    auction_fixed,
    esd_init,
    esd_state_update,
    heu_dispatch_jax,
    hybrid_dispatch_jax,
)


class TestJittableDispatchers:
    def test_heu_jax_matches_numpy(self, rng):
        C = rng.random((16, 4))
        order = np.argsort(
            -(np.partition(C, 1, 1)[:, 1] - np.partition(C, 1, 1)[:, 0]),
            kind="stable")
        want = heu_dispatch(C, 4, order=order)
        got = np.asarray(heu_dispatch_jax(jnp.asarray(C), 4))
        np.testing.assert_array_equal(got, want)

    def test_auction_fixed_caps(self, rng):
        C = jnp.asarray(rng.random((24, 4)), jnp.float32)
        a = np.asarray(auction_fixed(C, 6))
        assert (a >= 0).all()
        assert np.bincount(a, minlength=4).max() <= 6

    @pytest.mark.parametrize("alpha", [0.0, 0.5, 1.0])
    def test_hybrid_balanced(self, rng, alpha):
        m, n = 32, 4
        C = jnp.asarray(rng.random((m, n)), jnp.float32)
        a = np.asarray(hybrid_dispatch_jax(C, m, alpha))
        assert np.bincount(a, minlength=n).max() <= m // n

    def test_hybrid_tied_costs_respect_cap(self):
        """Regression: auction tie wars leave stragglers, and the old
        fallback dumped them ALL on one argmin-loaded worker — 2x the
        capacity on duplicated-row cost matrices (the empty-cache first
        step), which the ragged wire then silently truncated."""
        m, n, cap = 32, 4, 8
        for seed in range(8):
            row = np.random.default_rng(seed).random((1, n))
            C = jnp.asarray(np.repeat(row, m, axis=0), jnp.float32)
            a = np.asarray(hybrid_dispatch_jax(C, m, 1.0, cap=cap))
            counts = np.bincount(a, minlength=n)
            assert counts.max() <= cap, (seed, counts)
            assert counts.sum() == m


class TestStateUpdate:
    def test_matches_cluster_cache(self, rng):
        """In-jit protocol == numpy ClusterCache (no capacity limit)."""
        n, V = 3, 40
        state = esd_init(n, V)
        cache = ClusterCache(n, V, capacity=V)  # no eviction
        for it in range(6):
            batches = [np.unique(rng.integers(0, V, 6)) for _ in range(n)]
            need = np.zeros((n, V), bool)
            for j, b in enumerate(batches):
                need[j, b] = True
            state, counts = esd_state_update(state, jnp.asarray(need))
            stats = cache.step(batches)
            np.testing.assert_array_equal(np.asarray(counts["miss_pull"]),
                                          stats.miss_pull, err_msg=f"it{it}")
            np.testing.assert_array_equal(np.asarray(counts["update_push"]),
                                          stats.update_push, err_msg=f"it{it}")
        np.testing.assert_array_equal(np.asarray(state.latest),
                                      cache.latest_in_cache)
        np.testing.assert_array_equal(np.asarray(state.dirty), cache.dirty)

    def test_capacity_evicts_lru(self, rng):
        n, V, cap = 2, 30, 6
        state = esd_init(n, V)
        for it in range(5):
            need = np.zeros((n, V), bool)
            need[0, it * 5:(it + 1) * 5] = True
            state, counts = esd_state_update(state, jnp.asarray(need), cap)
            assert int(np.asarray(state.latest[0]).sum()) <= cap
        assert int(np.asarray(counts["evict_push"]).sum()) >= 0


MULTIDEV_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import PartitionSpec as P
    from jax import shard_map
    from repro.core.dispatch_tpu import esd_dispatch, esd_init, need_matrix
    from repro.launch.mesh import make_mesh

    n, m, F, V = 8, 16, 4, 100
    mesh = make_mesh((n,), ("data",))
    rng = np.random.default_rng(0)
    samples = rng.integers(0, V, (n * m, F)).astype(np.int32)
    state = esd_init(n, V)
    t = jnp.asarray(np.where(np.arange(n) < 4, 1.0, 10.0), jnp.float32)

    def f(s):
        exch, assign = esd_dispatch(s, state, t, alpha=0.0)
        need = need_matrix(exch, "data", V)
        return exch, assign, need

    exch, assign, need = shard_map(
        f, mesh=mesh, in_specs=(P("data", None),),
        out_specs=(P("data", None), P("data"), P(None, None)),
        check_vma=False)(jnp.asarray(samples))
    exch, assign = np.asarray(exch), np.asarray(assign)

    # 1) every shard sends exactly m/n to each worker
    for sh in range(n):
        a = assign[sh * m:(sh + 1) * m]
        assert np.bincount(a, minlength=n).tolist() == [m // n] * n, a

    # 2) exchange preserves the multiset of samples
    orig = sorted(map(tuple, samples.tolist()))
    got = sorted(map(tuple, exch.reshape(-1, F).tolist()))
    assert orig == got, "exchange lost/duplicated samples"

    # 3) exchanged rows on worker j are exactly the rows assigned to j
    for j in range(n):
        sent = sorted(tuple(samples[i]) for i in range(n * m) if assign[i] == j)
        rec = sorted(map(tuple, exch[j * m:(j + 1) * m].tolist()))
        assert sent == rec, f"worker {j} mismatch"

    # 4) need matrix marks exactly the ids each worker received
    need = np.asarray(need)
    for j in range(n):
        ids = set(exch[j * m:(j + 1) * m].reshape(-1).tolist())
        assert set(np.where(need[j])[0].tolist()) == ids
    print("MULTIDEV_OK")
""")


@pytest.mark.slow
def test_shard_map_dispatch_8dev():
    res = subprocess.run(
        [sys.executable, "-c", MULTIDEV_SCRIPT],
        capture_output=True, text=True, timeout=600,
        env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
             "HOME": os.environ.get("HOME", ""),
             # the script wants 8 *host* devices; keep jax off any real
             # accelerator the machine happens to have
             "JAX_PLATFORMS": os.environ.get("JAX_PLATFORMS", "cpu")},
        cwd=Path(__file__).resolve().parents[1],
    )
    assert "MULTIDEV_OK" in res.stdout, res.stdout + res.stderr
