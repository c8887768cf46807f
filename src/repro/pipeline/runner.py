"""Pipelined ESD training executor.

Splits one ESD training step into three stages and software-pipelines
them across iterations:

  decide   assign_t            = decide_fn(esd_state, batch_t)
  advance  (x_t, state_t, aux) = advance_fn(state_{t-1}, batch_t, assign_t)
  train    loss_t              = train_fn(x_t)

The decide/advance chain (Alg. 1 cost matrix + hybrid assignment +
sample exchange + cache-state update) never reads the model parameters,
so it can run ahead of training: with ``depth = d`` the runner keeps up
to ``d - 1`` advanced steps in flight before it blocks on a train
result.  All three stages are jax-jitted device computations, so
"running ahead" costs no threads — jax's async dispatch queues the
chain for steps t+1.. while the device still executes step t's
forward/backward, which is exactly the paper's decision hiding
(dispatch latency leaves the critical path once it fits under a train
step).

``depth=1`` is the synchronous loop.  Because every stage is the same
jitted function with the same inputs in either mode, the pipelined
schedule is *bitwise identical* to the synchronous one — only the host's
issue order changes.  That equivalence is pinned by the test suite and
is the backbone invariant of the subsystem.

``stale=True`` switches decide to the :class:`DoubleBuffer`'s back slot:
the decision for step t is computed on the state of step t-2, removing
its data dependency on step t-1's cache update so it can overlap even
that.  The decision may then be off by a bounded amount
(``double_buffer.staleness_bound``); on commit the runner applies the
correction — it re-scores the chosen assignment against the committed
state via ``realized_cost_fn`` and records both numbers, so consumers
always account cost at the realized value, never the stale estimate.

``decide_ahead=A`` (A >= 1) generalizes the stale mode into a
*decide-ahead chain*: the runner keeps up to ``A + 1`` decisions
buffered, so the assignment for step t+a (a <= A) is computed on the
state committed a steps earlier — progressively stale along the chain,
which is what lets the decision stream stay ahead of training at
``depth > 2`` where the one-slot stale mode would re-serialize.  The
per-sample decision error is bounded by the *chained* staleness bound
(``double_buffer.staleness_bound_chain``: one term per intervening
commit).  On commit the runner first hands the stale assignment to
``repair_fn`` (if given), which re-assigns exactly the samples whose
ids' state columns changed since decide time — cheaper than a full
re-decide, and together with ``realized_cost_fn`` it keeps accounting
at committed-state truth.  ``decide_ahead=0`` is the unchanged
(bitwise) PR 5 path.

Stage contracts (all device-array friendly):
  * ``decide_fn(esd_state, batch) -> (assign, alg1_est | None)`` —
    ``alg1_est`` is the Alg.-1 objective of the chosen assignment under
    the decide-time state (a scalar), or None if not tracked.
  * ``advance_fn(esd_state, batch, assign) -> (train_input, new_state,
    aux)`` — ``aux`` is an arbitrary pytree of per-step accounting
    (e.g. transmission counts), handed back on drain.
  * ``train_fn(train_input) -> loss`` — owns the parameter/optimizer
    state (closure); returns the scalar loss.
  * ``realized_cost_fn(state, batch, assign) -> scalar`` (optional) —
    the commit-time re-score used by the stale/decide-ahead modes.
  * ``repair_fn(committed_state, decide_state, batch, assign) ->
    (assign, info_dict)`` (optional, decide-ahead mode) — re-assigns the
    samples whose ids changed state between the two states; its info
    entries (e.g. ``n_reassigned``) merge into the step's record info.
"""
from __future__ import annotations

from collections import deque
from typing import Any, Callable, Iterable, Optional

import jax

from repro.obs.trace import get_tracer

from .double_buffer import db_commit, db_init

__all__ = ["PipelinedRunner"]

# Tracing semantics (host wall time; a no-op unless a tracer is installed
# via repro.obs, so the traced and untraced loops are bitwise identical).
# The main thread's spans tile the loop and never nest, so whatever the
# host does between two device programs falls in exactly one of them:
#   * "batch.next": pulling the next batch (loader wait, lookahead
#     window, device_put) — track "batch".
#   * "decide" / "repair" / "realized" / "advance": issue time of their
#     jitted stage (jax dispatches asynchronously; these return before
#     the device finishes) — track "decide".
#   * "train.issue" (the train_fn call), "loss.wait" (the block on the
#     step's loss) and "record" (record_fn) — the drain of a step, on
#     that step's "train/<t mod depth>" track.
# "train" is the *in-flight window* of a step: opened when the step's
# chain is fully issued (it enters `pending`) and closed when its drain
# completes.  Windows of consecutive steps overlap at depth >= 2, so each
# lives on its own per-slot track — decide spans for later steps fall
# inside them, which is exactly the decision hiding the exported trace
# should show.  Spans a stage opens itself (e.g. "prefetch.pull") nest
# inside that stage's span.

_END = object()


def _next_batch(tr, it, step: int):
    """``next(it)`` under the "batch.next" span; ``_END`` when exhausted."""
    with tr.span("batch.next", track="batch", step=step):
        return next(it, _END)


class PipelinedRunner:
    def __init__(self, decide_fn: Callable, advance_fn: Callable,
                 train_fn: Callable, esd_state: Any, depth: int = 1,
                 stale: bool = False,
                 realized_cost_fn: Optional[Callable] = None,
                 decide_ahead: int = 0,
                 repair_fn: Optional[Callable] = None):
        if depth < 1:
            raise ValueError(f"depth must be >= 1, got {depth}")
        if stale and depth < 2:
            raise ValueError("stale decisions only make sense pipelined "
                             "(depth >= 2): at depth 1 the committed state "
                             "is always available")
        if decide_ahead < 0:
            raise ValueError(f"decide_ahead must be >= 0, got {decide_ahead}")
        if decide_ahead and stale:
            raise ValueError("decide_ahead subsumes stale (the chain decides "
                             "on progressively stale states already); pick "
                             "one")
        if repair_fn is not None and not decide_ahead:
            raise ValueError("repair_fn only applies to decide-ahead chains "
                             "(decide_ahead >= 1)")
        self.decide_fn = decide_fn
        self.advance_fn = advance_fn
        self.train_fn = train_fn
        self.esd_state = esd_state
        self.depth = depth
        self.stale = stale
        self.realized_cost_fn = realized_cost_fn
        self.decide_ahead = decide_ahead
        self.repair_fn = repair_fn

    def run(self, batches: Iterable[Any], steps: Optional[int] = None,
            record_fn: Optional[Callable] = None) -> list:
        """Drive the pipeline over ``batches`` (at most ``steps`` of them).

        ``record_fn(t, loss, aux, info) -> record`` builds one output
        record per step at drain time (the sync point — convert device
        values to python there); default records ``{"step", "loss"}``.
        ``info`` carries the decision metrics: ``alg1_est`` when the
        decide stage tracks it, plus ``alg1_realized`` (the commit-time
        correction) in stale mode.
        """
        if self.decide_ahead:
            return self._run_ahead(batches, steps, record_fn)
        tr = get_tracer()
        it = iter(batches)
        pending: deque = deque()
        records = []
        # stale mode rotates the two-slot DoubleBuffer; exact mode keeps
        # a single committed state (the back slot would pin a second full
        # EsdState alive for nothing)
        db = db_init(self.esd_state) if self.stale else None
        state = self.esd_state
        t = 0
        while steps is None or t < steps:
            batch = _next_batch(tr, it, t)
            if batch is _END:
                break
            committed = db.front if self.stale else state
            decide_state = db.back if self.stale else state
            with tr.span("decide", track="decide", step=t):
                assign, alg1_est = self.decide_fn(decide_state, batch)
            info = {}
            if alg1_est is not None:
                info["alg1_est"] = alg1_est
            if self.stale and self.realized_cost_fn is not None:
                # the bounded correction: re-score the stale decision on
                # the committed state the step actually runs against
                # (what an exact decide would have read)
                with tr.span("realized", track="decide", step=t):
                    info["alg1_realized"] = self.realized_cost_fn(
                        committed, batch, assign)
            with tr.span("advance", track="decide", step=t):
                train_input, new_state, aux = self.advance_fn(
                    committed, batch, assign)
            if self.stale:
                db = db_commit(db, new_state)
            state = new_state
            pending.append((t, train_input, aux, info,
                            tr.start_span("train",
                                          track=f"train/{t % self.depth}",
                                          step=t)))
            # keep at most depth-1 advanced steps in flight ahead of train
            while len(pending) >= self.depth:
                records.append(self._drain_one(pending, record_fn))
            t += 1
        while pending:
            records.append(self._drain_one(pending, record_fn))
        self.esd_state = state
        return records

    def _run_ahead(self, batches: Iterable[Any], steps: Optional[int],
                   record_fn: Optional[Callable]) -> list:
        """Decide-ahead chain: keep up to ``decide_ahead + 1`` decisions
        buffered, each made on the newest state committed at its decide
        time — so the decision for step t+a is a commits stale, and the
        decide stream never blocks on the advance chain."""
        tr = get_tracer()
        it = iter(batches)
        ahead = self.decide_ahead
        pending: deque = deque()
        decided: deque = deque()   # (batch, assign, alg1_est, decide_state)
        records = []
        state = self.esd_state
        exhausted = False
        pulled = 0
        t = 0
        while steps is None or t < steps:
            while (len(decided) <= ahead and not exhausted
                   and (steps is None or pulled < steps)):
                batch = _next_batch(tr, it, pulled)
                if batch is _END:
                    exhausted = True
                    break
                with tr.span("decide", track="decide", step=pulled):
                    assign, alg1_est = self.decide_fn(state, batch)
                decided.append((batch, assign, alg1_est, state))
                pulled += 1
            if not decided:
                break
            batch, assign, alg1_est, decide_state = decided.popleft()
            info = {}
            if alg1_est is not None:
                info["alg1_est"] = alg1_est
            if self.repair_fn is not None:
                # re-assign only the samples whose ids changed state
                # between decide time and now; everything else keeps its
                # (still-exact) stale assignment
                with tr.span("repair", track="decide", step=t):
                    assign, repair_info = self.repair_fn(state, decide_state,
                                                         batch, assign)
                info.update(repair_info)
            if self.realized_cost_fn is not None:
                with tr.span("realized", track="decide", step=t):
                    info["alg1_realized"] = self.realized_cost_fn(
                        state, batch, assign)
            with tr.span("advance", track="decide", step=t):
                train_input, new_state, aux = self.advance_fn(state, batch,
                                                              assign)
            state = new_state
            pending.append((t, train_input, aux, info,
                            tr.start_span("train",
                                          track=f"train/{t % self.depth}",
                                          step=t)))
            while len(pending) >= self.depth:
                records.append(self._drain_one(pending, record_fn))
            t += 1
        while pending:
            records.append(self._drain_one(pending, record_fn))
        self.esd_state = state
        return records

    def _drain_one(self, pending: deque, record_fn: Optional[Callable]):
        t, train_input, aux, info, window = pending.popleft()
        tr = get_tracer()
        try:
            with tr.span("train.issue", track=window.track, step=t):
                loss = self.train_fn(train_input)
            with tr.span("loss.wait", track=window.track, step=t):
                jax.block_until_ready(loss)
            with tr.span("record", track=window.track, step=t):
                if record_fn is None:
                    return {"step": t, "loss": float(loss)}
                return record_fn(t, loss, aux, info)
        finally:
            window.end()
