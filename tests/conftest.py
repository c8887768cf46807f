"""Shared fixtures.  NOTE: no XLA_FLAGS here — tests see the real single
CPU device; multi-device tests spawn subprocesses that set the flag
themselves (see test_dispatch_tpu.py)."""
import numpy as np
import pytest


@pytest.fixture
def rng():
    return np.random.default_rng(0)


# --------------------------------------------------------------------------
# Test sharding: `--shard I/N` keeps every N-th collected test starting at
# I (0-based): run the N shards as concurrent pytest processes;
# round-robin over the collection order interleaves the heavy per-arch
# parameterizations, and the shards partition the full selection exactly.
# --------------------------------------------------------------------------
def pytest_addoption(parser):
    parser.addoption(
        "--shard", default=None, metavar="I/N",
        help="run only collected tests with index %% N == I (0-based); "
             "run the N shards as concurrent pytest processes on "
             "machines with real parallelism")


def pytest_collection_modifyitems(config, items):
    shard = config.getoption("--shard")
    if not shard:
        return
    try:
        idx, n = map(int, shard.split("/"))
    except ValueError as e:
        raise pytest.UsageError(f"--shard expects I/N, got {shard!r}") from e
    if n < 1 or not 0 <= idx < n:
        raise pytest.UsageError(
            f"--shard {shard}: need N >= 1 and 0 <= I < N (0-based)")
    keep = [it for i, it in enumerate(items) if i % n == idx]
    drop = [it for i, it in enumerate(items) if i % n != idx]
    items[:] = keep
    if drop:
        config.hook.pytest_deselected(items=drop)
