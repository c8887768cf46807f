"""What the benchmark hands the program under test, and how it listens.

The program's entry points look a configuration up by name in
``repro.configs.DLRM_CONFIGS`` and its sample stream in
``repro.data.synthetic.WORKLOADS``.  The benchmark registers its own
entries there at run time, so the program runs the benchmark's sizes
and trains from the benchmark's generator, and no program file is
edited.  ``patched`` swaps module attributes for the length of a run
and puts them back.
"""
from __future__ import annotations

import contextlib
import dataclasses
from typing import Any

from repro.configs import DLRM_CONFIGS
from repro.configs.dlrm_configs import DLRMConfig
from repro.data.synthetic import WORKLOADS, CTRWorkload

from .traffic import CTRSampler


@dataclasses.dataclass(frozen=True)
class BenchWorkload(CTRWorkload):
    """The program's workload type, drawing from the benchmark's sampler.
    ``feed`` supplies the training stream (and ends it)."""

    sampler: Any = dataclasses.field(default=None, compare=False)
    feed: Any = dataclasses.field(default=None, compare=False)

    def sample_batch(self, rng, batch):
        return self.sampler.sparse(rng, batch)

    def dense_batch(self, rng, batch):
        return self.sampler.dense(rng, batch)

    def label_batch(self, rng, batch):
        return self.sampler.labels(rng, batch)

    def stream(self, seed, batch):
        return self.feed.stream(self.sampler, seed, batch)


def _fields(cls, values) -> dict:
    """The entries of ``values`` that name a field of dataclass ``cls``,
    with lists as tuples."""
    names = {f.name for f in dataclasses.fields(cls)}
    return {k: tuple(v) if isinstance(v, list) else v
            for k, v in values.items() if k in names}


@contextlib.contextmanager
def registered(name: str, cfg: dict, sampler: CTRSampler, feed=None):
    """Register configuration ``cfg``, drawing its samples from
    ``sampler``, under ``bench.<name>`` for the length of the block;
    yields the name the program's ``--arch`` takes.

    Every key of ``cfg`` that names a field of the program's
    ``DLRMConfig`` reaches it as it stands (``cross_layers`` is 0 where
    it states none), and every attribute of ``sampler`` that names
    a field of ``CTRWorkload`` reaches the workload, so a field a later
    program adds needs no edit here."""
    key = f"bench.{name}"
    given = {f.name: getattr(sampler, f.name)
             for f in dataclasses.fields(CTRWorkload)
             if hasattr(sampler, f.name)}
    WORKLOADS[key] = BenchWorkload(**_fields(CTRWorkload, given) | dict(
        name=key, model=cfg["kind"], sampler=sampler, feed=feed))
    DLRM_CONFIGS[key] = DLRMConfig(**_fields(
        DLRMConfig, {"cross_layers": 0} | cfg) | dict(
        name=key, workload=key, n_dense=sampler.n_dense))
    try:
        yield key
    finally:
        del WORKLOADS[key], DLRM_CONFIGS[key]


@contextlib.contextmanager
def patched(module, **attrs):
    """Set ``module.<name> = value`` for each keyword, restore on exit."""
    old = {k: getattr(module, k) for k in attrs}
    for k, v in attrs.items():
        setattr(module, k, v)
    try:
        yield
    finally:
        for k, v in old.items():
            setattr(module, k, v)


TRAIN_STATE = ("params", "opt_state")


def closure_vars(fn) -> dict:
    """The variables a closure reads from its enclosing function, by name
    (cells not yet assigned are left out)."""
    out = {}
    for name, cell in zip(fn.__code__.co_freevars, fn.__closure__ or ()):
        try:
            out[name] = cell.cell_contents
        except ValueError:
            pass
    return out


def train_state(train_fn) -> dict:
    """The live training state of ``run_dlrm``: ``params`` and
    ``opt_state``, which its train closure reads from the enclosing
    function.  ``run_dlrm`` hands no state back, so this is where the
    check reads it.  A program whose closure no longer holds them fails
    here, by name, and not later as a wrong number."""
    got = closure_vars(train_fn)
    missing = [k for k in TRAIN_STATE if k not in got]
    if missing:
        raise RuntimeError(
            f"run_dlrm's train closure no longer holds {missing} (it holds "
            f"{sorted(got)}): the benchmark's check reads the training "
            f"state there; hand it over another way and read it in "
            f"bench/train_cell.py")
    return {k: got[k] for k in TRAIN_STATE}
