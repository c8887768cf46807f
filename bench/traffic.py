"""Traffic generator of the benchmark: CTR sample batches drawn from a
seed.

It reproduces ``repro.data.synthetic.CTRWorkload`` array for array (a
test holds it to that), with one change: the truncated-Zipf CDF of each
(skew, table size) pair is built once per sampler instead of on every
draw, so the host pays for sampling and not for rebuilding CDFs.
"""
from __future__ import annotations

import numpy as np

from . import models

PAD_ID = -1


def sampler(cfg: dict, root=None):
    """The sample generator of ``cfg``'s model kind over its ``tables``
    block: the ``Sampler`` its module names, else :class:`CTRSampler`."""
    kind = models.load(cfg["kind"], root)
    return getattr(kind, "Sampler", CTRSampler)(cfg["tables"])


class CTRSampler:
    """Sparse ids, dense features and labels of one configuration's
    sample stream.  ``spec`` holds the configuration's ``tables`` block:
    ``sizes``, ``zipf_a``, ``n_dense``, ``n_groups``, ``group_frac``,
    ``hist_max`` and ``hist_mean``."""

    def __init__(self, spec: dict):
        self.sizes = tuple(int(s) for s in spec["sizes"])
        self.zipf_a = tuple(float(a) for a in spec["zipf_a"])
        if len(self.sizes) != len(self.zipf_a):
            raise ValueError("tables: sizes and zipf_a differ in length")
        self.n_dense = int(spec["n_dense"])
        self.n_groups = int(spec["n_groups"])
        self.group_frac = float(spec["group_frac"])
        self.hist_max = int(spec["hist_max"])
        self.hist_mean = float(spec["hist_mean"])
        self.offsets = np.concatenate(
            [[0], np.cumsum(self.sizes)[:-1]]).astype(np.int64)
        self._cdfs: dict[tuple[float, int], np.ndarray] = {}

    @property
    def table_sizes(self) -> tuple:
        return self.sizes

    @property
    def n_fields(self) -> int:
        return len(self.sizes)

    @property
    def width(self) -> int:
        return self.n_fields + self.hist_max

    @property
    def vocab(self) -> int:
        return int(sum(self.sizes))

    def zipf(self, rng: np.random.Generator, a: float, size: int,
             vocab: int) -> np.ndarray:
        """Zipf(a) truncated to [0, vocab), by inverse CDF."""
        cdf = self._cdfs.get((a, vocab))
        if cdf is None:
            w = np.arange(1, vocab + 1, dtype=np.float64) ** (-a)
            cdf = np.cumsum(w)
            cdf /= cdf[-1]
            self._cdfs[(a, vocab)] = cdf
        return np.searchsorted(cdf, rng.random(size)).astype(np.int64)

    def _local(self, rng, a, n, size, groups, ids):
        """Group-local redraw of ``ids``: the same Zipf shape inside each
        sample's group slice, taken with probability ``group_frac``."""
        if size < 10 * self.n_groups or self.group_frac <= 0:
            return ids
        slice_size = size // self.n_groups
        local = groups * slice_size + self.zipf(rng, a, n, slice_size)
        return np.where(rng.random(n) < self.group_frac, local, ids)

    def sparse(self, rng: np.random.Generator, batch: int) -> np.ndarray:
        """(batch, width) flat ids: one per field, then the history bag
        over field 0's table (PAD = -1 past each bag's length)."""
        groups = rng.integers(0, self.n_groups, batch)
        cols = []
        for f, (size, a) in enumerate(zip(self.sizes, self.zipf_a)):
            ids = self.zipf(rng, a, batch, size)
            cols.append(self._local(rng, a, batch, size, groups, ids)
                        + self.offsets[f])
        out = np.stack(cols, axis=1)
        if not self.hist_max:
            return out
        size, a, H = self.sizes[0], self.zipf_a[0], self.hist_max
        lengths = np.minimum(rng.geometric(1.0 / self.hist_mean, batch), H)
        hist = self.zipf(rng, a, batch * H, size)
        hist = self._local(rng, a, batch * H, size, np.repeat(groups, H), hist)
        hist = hist.reshape(batch, H) + self.offsets[0]
        hist[np.arange(H)[None, :] >= lengths[:, None]] = PAD_ID
        return np.concatenate([out, hist], axis=1)

    def dense(self, rng: np.random.Generator, batch: int) -> np.ndarray:
        return rng.standard_normal((batch, self.n_dense)).astype(np.float32)

    def labels(self, rng: np.random.Generator, batch: int) -> np.ndarray:
        return (rng.random(batch) < 0.25).astype(np.float32)

    def batches(self, seed: int, batch: int):
        """Endless ``(sparse, dense, labels)`` batches from ``seed``."""
        rng = np.random.default_rng(seed)
        while True:
            yield (self.sparse(rng, batch), self.dense(rng, batch),
                   self.labels(rng, batch))
