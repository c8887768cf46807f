"""Model kinds of the benchmark, one file each: ``bench/models/<kind>.py``,
found by the configuration's ``kind`` and loaded by path from the
cell's root, as the per-layer readers in ``bench/metrics/`` are.

A kind module holds the plain reference of one model and its required
work, and nothing of the program under test:

- ``tables(cfg)``: the table leaves of its parameters, in order, each
  with its row width (one row per id of the flat table).
- ``init(model, dtype, key)``: its weights from ``key``, stored in
  ``dtype`` (run inside ``reference._init``'s jit).
- ``forward(model, params, sparse, dense)``: logits (B,) of a batch of
  flat ids (PAD = -1) and dense features.
- ``dense_params(cfg)`` and ``forward_flops(cfg, rows)``: the required
  work outside the tables (``bench/work.py`` adds the touched rows).

and may name ``Sampler``: its sample generator, built on the
configuration's ``tables`` block (``bench.traffic.CTRSampler`` where it
names none).  ``bench/models/_ctr.py`` holds what the CTR kinds share.

``cfg`` is the configuration file's JSON object; ``model`` is
``bench.reference.Model``, which carries its keys.  A call given no
``root`` finds the kind in the checkout whose ``bench`` package runs:
under ``bench/run.py`` that is the cell's own, so the per-layer readers,
which call ``bench.work`` without a root, count the cell's kind.
"""
from __future__ import annotations

import importlib.util
import re
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]

_loaded: dict[Path, object] = {}


def load(kind: str, root=None):
    """The module of model kind ``kind`` under ``root`` (the checkout
    this file is in by default), loaded once per path."""
    path = (Path(root or ROOT) / "bench" / "models" / f"{kind}.py").resolve()
    if path not in _loaded:
        if not path.is_file():
            raise ValueError(f"unknown model kind {kind!r}: no {path}")
        spec = importlib.util.spec_from_file_location(
            "bench_kind_" + re.sub(r"\W", "_", kind), path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        _loaded[path] = mod
    return _loaded[path]
