"""Training cells: ``repro.launch.train.run_dlrm`` as its command line
runs it, on the benchmark's configuration and stream.

One call of ``run_dlrm`` is the whole run.  Its first ``warmup_steps``
steps are set-up: they compile every stage, and the first three of them
are the steps the reference follows (after step 1 the optimizer's state
gives the first gradient and the parameters their change, after step 3
the parameters give their change and the untouched rows their drift).
The window opens when the last warm-up step is recorded and the stream
ends ``seconds`` later; the steps still in the pipeline then
drain, and the window closes with the last of them.  ``run_dlrm``
records every step after syncing on its loss, so the window is timed at
those syncs.
"""
from __future__ import annotations

import gc
import shutil
import tempfile
import threading
import time
from functools import partial
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from . import checks, program, reference, scopes, work, xplane
from .traffic import sampler as kind_sampler


class Feed:
    """The stream ``run_dlrm`` trains from, and the window's clock."""

    def __init__(self, warmup_steps: int, seconds: float, trace: bool):
        self.warmup_steps = warmup_steps
        self.seconds = seconds
        self.trace = trace
        self.t_open = None          # host time the window opened
        self.stop_at = None         # host time the stream ends
        self.record_t = {}          # step -> host time of its record
        self.distinct = []          # distinct ids per batch (trace runs)
        self.profiling = None       # trace directory while profiling
        self.mark_t = None
        self._lock = threading.Lock()

    def stream(self, sampler, seed, batch):
        for b in sampler.batches(seed, batch):
            with self._lock:
                now = time.perf_counter()
                if self.stop_at is not None and now >= self.stop_at:
                    return
                if self.trace and self.t_open is not None:
                    self.distinct.append(work.distinct_ids(b[0]))
            yield b

    def on_record(self, step: int) -> None:
        now = time.perf_counter()
        self.record_t[step] = now
        if step != self.warmup_steps - 1:
            return
        if self.trace:
            self.profiling = tempfile.mkdtemp(prefix="bench_trace_")
            jax.profiler.start_trace(self.profiling)
            with jax.profiler.TraceAnnotation(xplane.MARK):
                self.mark_t = time.perf_counter()
            now = self.mark_t
        with self._lock:
            self.t_open = now
            self.stop_at = now + self.seconds


class Snapshots:
    """Reads the program's live training state after its first and third
    steps: the first gradient's per-leaf norm from the row-wise Adagrad
    accumulators, the per-leaf norm of the parameters' change from the
    seed's weights after each, and after the third the norm of each
    table's change on the rows no step touched (all but ``rows``)."""

    def __init__(self, model, seed, rows):
        self.model, self.seed = model, seed
        self.rows = jnp.asarray(rows, jnp.int32)
        self.calls = 0
        self.grad_norm = self.delta1_norm = self.delta_norm = None
        self.drift = None

    def after_train(self, train_fn) -> None:
        self.calls += 1
        if self.calls not in (1, 3):
            return
        state = program.train_state(train_fn)
        params = state["params"]
        change, drift = _state_norms(self.model, params,
                                     jax.random.key(self.seed), self.rows)
        if self.calls == 1:
            self.grad_norm = _floats(_acc_grad_norms(params,
                                                     state["opt_state"]))
            self.delta1_norm = _floats(change)
        else:
            self.delta_norm = _floats(change)
            self.drift = _floats(drift)


def _floats(tree) -> dict:
    return {k: float(v) for k, v in tree.items()}


@jax.jit
def _acc_grad_norms(params, acc):
    """After one row-wise Adagrad step a row's accumulator is the mean
    square of its gradient, so a leaf's gradient norm is
    sqrt(sum(acc) * row width)."""
    flat = jax.tree_util.tree_flatten_with_path(params)[0]
    accs = jax.tree_util.tree_structure(params).flatten_up_to(acc)
    return {jax.tree_util.keystr(k): jnp.sqrt(
        jnp.sum(a) * (p.shape[-1] if p.ndim >= 2 else 1))
        for (k, p), a in zip(flat, accs)}


@partial(jax.jit, static_argnums=(0,))
def _state_norms(model, params, key, rows):
    """Per leaf, the norm of the change from the seed's float32 weights
    (made here, inside the reduction, so no second table is held); per
    table, that norm over the rows not in ``rows``."""
    p0 = reference._init(model, jnp.float32, key)
    change, drift = {}, {}
    for path, p in jax.tree_util.tree_flatten_with_path(params)[0]:
        k = jax.tree_util.keystr(path)
        d = p.astype(jnp.float32) - _at(p0, path)
        if path[0].key in model.table_leaves:
            per_row = jnp.sum(jnp.square(d), axis=-1)
            change[k] = jnp.sqrt(jnp.sum(per_row))
            drift[k] = jnp.sqrt(jnp.sum(per_row.at[rows].set(0.0)))
        else:
            change[k] = jnp.sqrt(jnp.sum(jnp.square(d)))
    return change, drift


def _at(tree, path):
    for entry in path:
        tree = tree[getattr(entry, "key", getattr(entry, "idx", None))]
    return tree


def run(name: str, cfg: dict, traffic: dict, seed: int, seconds: float,
        trace: bool, device, root=None) -> dict:
    from repro.launch import train as T
    from repro.obs import MetricsRegistry, Tracer, set_tracer

    warmup = int(traffic["warmup_steps"])
    feed = Feed(warmup, float(traffic["trace_seconds"]) if trace else seconds,
                trace)
    model = reference.Model(cfg, root)
    k = int(traffic["batch_per_worker"]) * device["count"]
    # the stream's first three batches: the steps the reference follows
    sampler = kind_sampler(cfg, root)
    batches = [b for _, b in zip(range(3), sampler.batches(seed + 1, k))]
    # the touched rows, padded with a repeat to the batches' id slots: one
    # shape for every seed, so set-up compiles its reads once per cell
    rows = reference.touched_rows(batches)
    rows = np.pad(rows, (0, sum(b[0].size for b in batches) - rows.size),
                  mode="edge")
    snaps = Snapshots(model, seed, rows)
    compiles = checks.CompileCounter()

    class Registry(MetricsRegistry):
        def record_step(self, step, fields):
            rec = super().record_step(step, fields)
            feed.on_record(step)
            return rec

    class Runner(T.PipelinedRunner):
        def __init__(self, decide_fn, advance_fn, train_fn, *a, **kw):
            def train(x):
                loss = train_fn(x)
                snaps.after_train(train_fn)
                return loss
            super().__init__(decide_fn, advance_fn, train, *a, **kw)

    args = T.build_parser().parse_args(
        ["--arch", name, "--steps", str(10 ** 9), "--seed", str(seed),
         "--batch-per-worker", str(traffic["batch_per_worker"]),
         "--lr", str(traffic["lr"]),
         "--esd-alpha", str(traffic["esd_alpha"]),
         "--exchange", traffic["exchange"],
         "--pipeline-depth", str(traffic["pipeline_depth"]),
         "--lookahead", str(traffic["lookahead"]),
         "--prefetch", str(traffic["prefetch"]),
         "--prefetch-slots", str(traffic["prefetch_slots"]),
         "--capacity-ratio", str(traffic["capacity_ratio"]),
         "--log-every", str(10 ** 9)])
    args.verbose = False
    tracer = Tracer(capacity=1 << 20) if trace else None
    prev = set_tracer(tracer) if trace else None
    try:
        with program.registered(name, cfg, sampler, feed) as arch, \
                program.patched(T, MetricsRegistry=Registry,
                                PipelinedRunner=Runner), compiles.watch():
            args.arch = arch
            recs = T.run_dlrm(args)
        t_close = time.perf_counter()
    finally:
        if trace:
            set_tracer(prev)
        if feed.profiling is not None:
            jax.profiler.stop_trace()
    steps = [r["step"] for r in recs if r["step"] >= warmup]
    out = {"setup_end": feed.t_open, "attempted": len(steps), "failed": 0,
           "memory_peak_bytes": checks.peak_bytes()}
    window_s = feed.record_t[steps[-1]] - feed.t_open if steps else 0.0
    if not trace:
        out["metrics"] = {"train_samples_per_s": len(steps) * k / window_s
                          if window_s > 0 else 0.0}
    losses = [r["loss"] for r in recs[:3]]
    overflow = sum(int(r.get("exchange_overflow", 0)) for r in recs)
    compiles_in_window = compiles.after(feed.t_open)
    prog = {"loss": losses} | {
        key: {k_: float(v) for k_, v in getattr(snaps, key).items()}
        for key in ("grad_norm", "delta1_norm", "delta_norm", "drift")}
    if trace:
        out["trace"] = _reduce(feed, tracer, t_close, cfg, k, steps, recs,
                               device)
    del recs, snaps, Runner, Registry
    gc.collect()
    # the reference runs once the program's state is freed
    ref = reference.train_steps(model, seed, batches, float(traffic["lr"]))
    out["checks"] = checks.train_numbers(prog, ref) | {
        "overflow_rows": overflow, "compiles_in_window": compiles_in_window}
    return out


def _reduce(feed, tracer, t_close, cfg, k, steps, recs, device):
    """Per-layer context of a traced run: the trace by XLA module and
    operation (``reduced``), the exclusive device seconds of each
    ``jax.named_scope`` (``scope_s``, mean over chips) and the host
    seconds of each of the tracer's spans on the main thread
    (``host_phase_s``), all over the window."""
    try:
        path = next(Path(feed.profiling).glob("plugins/profile/*/*.xplane.pb"))
        planes = xplane.read_planes(path)
        space = scopes.read_space(path)
    finally:
        shutil.rmtree(feed.profiling, ignore_errors=True)
    offset = xplane.host_offset(planes, feed.mark_t)
    events = tracer.events()
    # the host's own work on the main thread names a gap before the
    # loader thread's sampling, which runs beside everything
    spans = [(e["name"], tracer.t0 + e["ts"], tracer.t0 + e["ts"] + e["dur"],
              0 if e["thread"] == "MainThread" else 1)
             for e in events if e["name"] != "train"]
    window = (feed.mark_t * 1e9 + offset, t_close * 1e9 + offset)
    red = xplane.reduce(planes, spans, offset, window_ns=window)
    named = scopes.reduce(space, window, names={e["name"] for e in events})
    pulled = sum(r.get("prefetch_bytes", 0) for r in recs
                 if r["step"] >= feed.warmup_steps)
    row_bytes = work.F32 * int(cfg["embedding_dim"])
    distinct = float(np.mean(feed.distinct)) if feed.distinct else 0.0
    return {"reduced": red, "steps": len(steps), "rows_per_step": k,
            "distinct_per_step": distinct,
            "rows_pulled": pulled / row_bytes, "cfg": cfg,
            "peaks": work.peaks(device["kind"]),
            "scope_s": named.scope_s, "host_phase_s": named.host_s}
