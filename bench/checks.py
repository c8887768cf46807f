"""The comparison that decides ``correct``: the numbers compared with the
reference, each against its limit, and what a run reads of the device.

Training compares the first three steps: each step's loss, the per-leaf
norm of the first gradient, and the per-leaf norm of the parameters'
change after the first step and after the three.  A leaf's gap is the
distance between the two norms over the reference's norm of that leaf
or of the median leaf, whichever is larger, and the worst leaf is
reported.  Leaves whose reference gradient is under a thousandth of the
median leaf's move by round-off alone under row-wise Adagrad, and are
left out of the change.  The tables' rows that no step touched have to
stay exactly as the seed made them: their change after the three steps
(the drift), over the reference's change of the table, is the drift
gap.
"""
from __future__ import annotations

import contextlib
import math
import time

import jax
import numpy as np

COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/backend_compile_duration")
ROUNDOFF_GRAD = 1e-3


class CompileCounter:
    """Host times at which jax traced or compiled a program."""

    def __init__(self):
        self.times = []

    def _listen(self, event, duration, **kw):
        if event in COMPILE_EVENTS:
            self.times.append(time.perf_counter())

    @contextlib.contextmanager
    def watch(self):
        jax.monitoring.register_event_duration_secs_listener(self._listen)
        try:
            yield self
        finally:
            jax.monitoring.unregister_event_duration_listener(self._listen)

    def after(self, t) -> int:
        return sum(1 for x in self.times if t is not None and x >= t)


def peak_bytes() -> int:
    """Peak bytes in use on the fullest device (0 where not reported)."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.local_devices()]
    return int(max(peaks))


def _leaf_gap(prog: dict, ref: dict, keep=None):
    names = [k for k in ref if keep is None or k in keep]
    if not names:
        return 0.0, ""
    scale = float(np.median([ref[k] for k in names]))
    worst, where = -1.0, ""
    for k in names:
        p = prog.get(k, math.nan)
        gap = abs(p - ref[k]) / max(ref[k], scale)
        if not gap <= worst:             # NaN wins
            worst, where = gap, k
            if math.isnan(gap):
                break
    return worst, where


def train_numbers(prog: dict, ref: dict) -> dict:
    """Gaps of the program's (or a control's) first three steps from the
    reference's.  Each input has ``loss`` (3,); ``grad_norm``,
    ``delta1_norm`` and ``delta_norm`` (leaf -> norm); and ``drift``
    (table leaf -> norm of its change on the rows no step touched, 0 in
    the reference)."""
    lp, lr = np.asarray(prog["loss"], float), np.asarray(ref["loss"], float)
    gaps = (np.abs(lp - lr) / np.abs(lr) if lp.shape == lr.shape
            else np.full(lr.shape, math.inf))
    g_med = float(np.median(list(ref["grad_norm"].values())))
    moved = {k for k, v in ref["grad_norm"].items()
             if v >= ROUNDOFF_GRAD * g_med}
    grad_gap, g_leaf = _leaf_gap(prog["grad_norm"], ref["grad_norm"])
    change1_gap, d1_leaf = _leaf_gap(prog["delta1_norm"],
                                     ref["delta1_norm"], moved)
    change_gap, d_leaf = _leaf_gap(prog["delta_norm"], ref["delta_norm"],
                                   moved)
    d_med = float(np.median([ref["delta_norm"][k] for k in moved]))
    drifts = [v / max(ref["delta_norm"][k], d_med)
              for k, v in prog["drift"].items()]
    drift_gap = (math.nan if any(math.isnan(x) for x in drifts)
                 else max(drifts, default=math.inf))
    return {"loss0_gap": float(gaps[0]), "loss_gap": float(np.max(gaps)),
            "grad_gap": grad_gap, "change1_gap": change1_gap,
            "change_gap": change_gap, "drift_gap": drift_gap,
            "_where": {"grad_gap": g_leaf, "change1_gap": d1_leaf,
                       "change_gap": d_leaf}}


def judge(numbers: dict, limits: dict) -> tuple[bool, dict]:
    """``correct`` and the compared numbers beside their limits: every
    number that has a limit is compared, and one that is missing or not
    a number is not correct."""
    out = {k: {"value": numbers.get(k, math.nan), "limit": lim}
           for k, lim in limits.items()}
    return all(v["value"] <= v["limit"] for v in out.values()), out
