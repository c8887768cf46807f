"""End-to-end training driver (the runnable example entrypoint).

Two modes:
  * DLRM (paper workloads): PS-style sharded embedding table + replicated
    MLP over a (data, model) mesh, with ESD dispatch running as jitted
    stages (shard_map + static all_to_all) when ``--esd-alpha`` is set.
    The step is split decide / advance / train and driven by the
    repro.pipeline executor: ``--pipeline-depth 2`` lets the dispatch
    decision for step t+1 overlap step t's forward/backward (the paper's
    decision hiding; depth 1 is the synchronous loop and bitwise-equal),
    ``--lookahead W`` reports the W-batch window-dedup stats, and
    ``--stale-decide`` runs the double-buffered staleness-tolerant
    variant (decides on the t-1 cache state, re-scores on commit).
    ``--cap-slack`` (with ``--exchange ragged``) relaxes the per-worker
    dispatch capacity; workers then train uneven PAD-masked batches.
    ``--decide-ahead A`` buffers up to A+1 decisions on progressively
    stale states (chained staleness bound) with a commit-time repair
    that re-places only the samples whose ids changed state, and
    ``--prefetch B`` (with ``--lookahead``) stages up to B future-miss
    rows per step into the window-driven staging plane while training
    runs — per-step metrics then split misses into prefetch hits vs
    demand (``prefetch_bytes`` / ``demand_miss_bytes`` /
    ``prefetch_hit_rate``).  Logs per-step transmission counts/cost
    from the in-jit cache state machine.
  * LM (any assigned arch, reduced or full): standard data+tensor parallel
    next-token training on a synthetic Zipf token stream.

Examples (CPU, reduced configs):
  PYTHONPATH=src python -m repro.launch.train --arch wdl-tiny --steps 30 --esd-alpha 1
  PYTHONPATH=src python -m repro.launch.train --arch wdl-tiny --steps 30 \
      --esd-alpha 1 --pipeline-depth 2 --lookahead 4
  PYTHONPATH=src python -m repro.launch.train --arch smollm-360m --smoke --steps 5
"""
from __future__ import annotations

import argparse
import dataclasses
import sys
import time
from functools import partial
from itertools import count
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from ..checkpoint import restore_checkpoint, save_checkpoint
from ..configs import DLRM_CONFIGS, get_config
from ..core.dispatch_tpu import esd_init, esd_sparse_init
from ..core.simulator import DEFAULT_BANDWIDTHS, GBPS, hetero_ps_bandwidths
from ..data.loader import PrefetchLoader
from ..data.synthetic import WORKLOADS, token_stream
from ..dist.sharding import param_specs, to_shardings
from ..elastic import FaultPlan, cost_column_bias, effective_t
from ..obs import (MetricsRegistry, Tracer, get_tracer, log_step,
                   set_registry, set_tracer)
from ..pipeline import (LookaheadWindow, PipelinedRunner, prefetch_candidates,
                        prefetch_init, prefetch_step, staged_membership)
from .cache import use_compile_cache
from .mesh import make_host_mesh
from .steps import (make_dlrm_esd_stages, make_dlrm_repair_stage,
                    make_dlrm_train_jit)
from ..models import api, dlrm
from ..optim import get_optimizer
from ..ps import make_partition
from ..quant.codecs import (get_codec, quantize_with_feedback,
                            resolve_link_codecs, row_wire_bytes, ste)
from ..core.cost import transmission_time_codec
from .steps import raise_on_overflow


# --------------------------------------------------------------------------
# DLRM + ESD
# --------------------------------------------------------------------------
def run_dlrm(args):
    cfg = DLRM_CONFIGS[args.arch]
    wl = WORKLOADS[cfg.workload]
    mesh = make_host_mesh()
    n = mesh.shape["data"]
    m = args.batch_per_worker
    k = m * n
    V = wl.vocab
    use_esd = args.esd_alpha is not None
    capacity = int(args.capacity_ratio * V)
    sparse_esd = args.esd_engine == "sparse"
    if args.cap_slack > 0.0:
        if not use_esd:
            raise SystemExit("--cap-slack needs ESD (--esd-alpha)")
        if args.exchange != "ragged":
            raise SystemExit("--cap-slack > 0 needs --exchange ragged (the "
                             "padded all_to_all requires equal m/n groups)")
    if args.stale_decide and args.pipeline_depth < 2:
        raise SystemExit("--stale-decide needs --pipeline-depth >= 2")
    if (args.pipeline_depth > 1 or args.stale_decide) and not use_esd:
        raise SystemExit("--pipeline-depth > 1 / --stale-decide need ESD "
                         "(--esd-alpha): without dispatch there is no "
                         "decision stage to pipeline")
    if args.decide_ahead:
        if not use_esd:
            raise SystemExit("--decide-ahead needs ESD (--esd-alpha): the "
                             "chain buffers dispatch decisions")
        if args.stale_decide:
            raise SystemExit("--decide-ahead subsumes --stale-decide (the "
                             "chain decides on progressively stale states "
                             "already); pick one")
        if args.fault_plan:
            raise SystemExit("--decide-ahead with --fault-plan is not wired "
                             "(the elastic stages feed per-step fault arrays "
                             "to an in-order decide stream)")
    use_prefetch = args.prefetch > 0
    if use_prefetch:
        if not use_esd:
            raise SystemExit("--prefetch needs ESD (--esd-alpha): the split "
                             "miss accounting lives in the cache update)")
        if args.lookahead <= 0:
            raise SystemExit("--prefetch needs --lookahead > 0 (the window "
                             "meta is what names the future misses)")
        if args.n_ps > 1:
            raise SystemExit("--prefetch with --n-ps > 1 is not wired (the "
                             "staging plane gathers from the unstacked "
                             "table)")
        if args.fault_plan:
            raise SystemExit("--prefetch with --fault-plan is not wired")
        if args.prefetch_slots < args.prefetch:
            raise SystemExit("--prefetch-slots must be >= --prefetch (one "
                             "step's pulls must fit the plane)")
    plan = None
    if args.fault_plan:
        if not use_esd:
            raise SystemExit("--fault-plan needs ESD (--esd-alpha): faults "
                             "act through the dispatch stages")
        if args.exchange != "ragged":
            raise SystemExit("--fault-plan needs --exchange ragged (a dead "
                             "worker breaks the padded equal-groups "
                             "all_to_all)")
        plan = FaultPlan.parse(args.fault_plan, n, args.n_ps)
    if args.resume and args.ckpt_dir is None:
        raise SystemExit("--resume needs --ckpt-dir")
    codec = get_codec(args.codec)
    if codec is not None and use_esd and args.exchange != "ragged":
        raise SystemExit("--codec with ESD needs --exchange ragged (the "
                         "quantized sample wire rides the ragged executor)")
    if args.codec_policy != "uniform" and codec is None:
        raise SystemExit("--codec-policy bandwidth needs --codec (it picks "
                         "which codec the slow links drop to)")

    # multi-PS: partition the V-space (repro.ps), run ids/planes/tables in
    # the PS-linearized space, and cost each op at the owning shard's link
    part = make_partition(V, args.n_ps, args.ps_layout) if args.n_ps > 1 else None
    if part is not None and use_esd and not sparse_esd:
        raise SystemExit("--n-ps > 1 requires --esd-engine sparse "
                         "(the dense engine has no per-PS accounting)")
    if args.ps_hetero and part is None:
        raise SystemExit("--ps-hetero needs --n-ps > 1 (there is no "
                         "per-shard link to skew with a single PS)")
    V_space = part.linear_size if part is not None else V

    if part is not None:
        bw = (hetero_ps_bandwidths(n, part.n_ps) if args.ps_hetero
              else np.repeat(DEFAULT_BANDWIDTHS(n)[:, None], part.n_ps, axis=1))
    else:
        bw = DEFAULT_BANDWIDTHS(n)
    if codec is None:
        # untouched fp32 pricing (bitwise reference path)
        t_tran = jnp.asarray((cfg.embedding_dim * 4.0) / bw, jnp.float32)
    else:
        # per-link byte width folded into T_j — same pricing the
        # simulator's Alg.-1 term uses.  Note the actual wire ships ONE
        # uniform codec (--codec); a "bandwidth" policy prices the
        # per-link mix into the dispatch objective (fast links fp16,
        # slow links the codec) ahead of true per-link wire codecs.
        link_codecs = resolve_link_codecs(args.codec_policy, bw, codec)
        t_tran = jnp.asarray(
            transmission_time_codec(cfg.embedding_dim, bw, link_codecs),
            jnp.float32)
    optimizer = get_optimizer("rowwise_adagrad", args.lr)
    params = dlrm.init_params(jax.random.key(args.seed), cfg, wl)
    if part is not None:
        # shard the DLRM table over n_ps: (n_ps, max_rows, E) PS stack
        params = dlrm.ps_stack_tables(params, part)
    opt_state = optimizer.init(params)

    # PS-style placement: embedding/wide tables row-sharded over the data
    # axis (each worker holds a V/n slice, replicated if V doesn't divide
    # n), MLP stack replicated.
    shardings = to_shardings(param_specs(params, mesh=mesh), mesh)
    params = jax.device_put(params, shardings)
    batch_shd = lambda nd: NamedSharding(mesh, P(*(("data",) + (None,) * (nd - 1))))
    replicated = NamedSharding(mesh, P())

    # PAD-masked loss only when PAD rows can actually appear: capacity
    # slack skews batches, and under a fault plan a dead worker's
    # exchanged block comes back all-PAD.  On even batches the masked
    # mean equals the plain one, but the plain path stays the bitwise
    # reference.
    loss_fn = (dlrm.bce_loss_masked
               if args.cap_slack > 0.0 or plan is not None
               else dlrm.bce_loss)

    train_jit = make_dlrm_train_jit(cfg, optimizer, loss_fn,
                                    part=None if use_esd else part)

    # quantized PS push/pull (--codec): rows DOWN — workers compute on
    # the wire-dequantized tables (STE keeps the embedding gradient
    # alive through round()); grads UP — table gradients are pushed
    # through the codec with error feedback (the quantization residual
    # carries to the next step), and rowwise-adagrad sees the *applied*
    # g_hat so its per-row accumulator tracks reality.  codec=None never
    # builds or calls this function — train_jit above stays the bitwise
    # fp32 path.
    quant_keys = tuple(k for k in dlrm.TABLE_LEAVES if k in params)
    qres = (jax.device_put({k: jnp.zeros_like(params[k]) for k in quant_keys},
                           {k: shardings[k] for k in quant_keys})
            if codec is not None else None)

    @partial(jax.jit, donate_argnums=(0, 1, 2))
    @jax.named_scope("dlrm.train_step")
    def train_jit_q(params, opt_state, qres, sparse, dense, labels):
        if not use_esd and part is not None:
            sparse = part.to_linear(sparse)

        @jax.named_scope("dlrm.forward")
        def loss_q(p):
            qp = dict(p)
            for kk in quant_keys:
                qp[kk] = ste(p[kk], codec)
            return loss_fn(qp, cfg, sparse, dense, labels)

        loss, grads = jax.value_and_grad(loss_q)(params)
        grads, new_qres = dict(grads), {}
        for kk in quant_keys:
            grads[kk], new_qres[kk] = quantize_with_feedback(
                grads[kk], qres[kk], codec)
        with jax.named_scope("optim.update"):
            params, opt_state = optimizer.update(grads, opt_state, params)
        return params, opt_state, new_qres, loss

    esd = None
    if use_esd:
        # ESD: decide / advance / train stages driven by the pipelined
        # executor — depth 1 is the synchronous loop (bitwise-identical).
        # With a fault plan the elastic stage variants take three extra
        # per-step *array* inputs (link times, cost bias, active mask),
        # so membership churn never recompiles anything.
        decide_jit, advance_jit, realized_jit, out_rows = make_dlrm_esd_stages(
            mesh, n, m, V_space, t_tran, args.esd_alpha or 0.0, part=part,
            exchange=args.exchange, cap_slack=args.cap_slack,
            sparse_esd=sparse_esd, capacity=capacity if capacity < V else None,
            elastic=plan is not None,
            max_failures=plan.max_inactive() if plan is not None else 0,
            codec=codec)
        if sparse_esd:
            # L = out_rows*F ids per worker post-exchange (need_ids_list
            # width) — out_rows from the stage factory, so the slot-buffer
            # sizing can never drift from the advance stage's row count
            esd = esd_sparse_init(n, V_space, capacity if capacity < V else None,
                                  max_ids=out_rows * wl.width)
        else:
            esd = esd_init(n, V)
        # the dispatch state is replicated over the mesh, never parked
        # on the first device
        esd = jax.device_put(esd, replicated)

    start = 0
    if args.resume:
        tmpl = {"params": params, "opt": opt_state}
        if use_esd:
            tmpl["esd"] = esd
        if codec is not None:
            tmpl["qres"] = qres
        restored, start = restore_checkpoint(args.ckpt_dir, tmpl)
        params = jax.device_put(restored["params"], shardings)
        opt_state = jax.tree.map(jnp.asarray, restored["opt"])
        if use_esd:
            esd = jax.device_put(restored["esd"], replicated)
        if codec is not None:
            qres = jax.device_put(restored["qres"],
                                  {k: shardings[k] for k in quant_keys})
        if args.verbose:
            log_step({"resumed_from_step": start})
    if start >= args.steps:
        return []

    # unified metrics registry; the returned `metrics` list is its
    # legacy per-step view (same dict shapes as ever)
    reg = MetricsRegistry()
    set_registry(reg)
    metrics = reg.steps
    t_total = jnp.asarray(t_tran)
    last_t = time.perf_counter()
    esd_seen = {}   # step -> post-advance dispatch state, for checkpoints

    def record(i, loss, counts, meta, info, pulled=None):
        nonlocal last_t
        now = time.perf_counter()
        rec = {"loss": float(loss), "wall_s": round(now - last_t, 4)}
        last_t = now
        esd_snap = esd_seen.pop(i, None)
        if counts is not None:
            # loud failure on silent row loss: an undersized ragged
            # budget must never truncate the batch unnoticed
            rec["exchange_overflow"] = int(np.asarray(
                counts.get("exchange_overflow", 0)))
            raise_on_overflow(counts)
            base_ops = ("miss_pull", "update_push", "evict_push")
            ops = {op: np.asarray(counts[op]) for op in base_ops}
            if part is not None:
                # per-(worker, PS) ops x per-(worker, PS) link times
                rec["cost"] = float(sum(
                    (np.asarray(counts[op + "_ps"]) * np.asarray(t_total)).sum()
                    for op in base_ops))
            else:
                rec["cost"] = float(sum((ops[o] * np.asarray(t_total)).sum()
                                        for o in ops))
            rec.update({op: int(v.sum()) for op, v in ops.items()})
            # miss-traffic split: with the staging plane active, a miss
            # whose row was already staged left the critical path — only
            # demand misses pay wire latency at train time (prefetch off:
            # every miss is a demand miss, prefetch_bytes 0)
            wire = row_wire_bytes(cfg.embedding_dim, codec)
            hit = (int(np.asarray(counts["prefetch_hit"]).sum())
                   if "prefetch_hit" in counts else 0)
            demand = (int(np.asarray(counts["demand_miss"]).sum())
                      if "demand_miss" in counts
                      else int(ops["miss_pull"].sum()))
            rec["prefetch_bytes"] = (int(np.asarray(pulled)) * wire
                                     if pulled is not None else 0)
            rec["demand_miss_bytes"] = demand * wire
            rec["prefetch_hit_rate"] = round(hit / max(hit + demand, 1), 4)
        if meta is not None:
            rec["window_dedup_frac"] = round(meta.dedup_frac, 4)
        for key in ("alg1_est", "alg1_realized"):
            if key in info:
                rec[key] = float(info[key])
        if "n_reassigned" in info:
            rec["n_reassigned"] = int(np.asarray(info["n_reassigned"]))
        if plan is not None:
            rec["n_active"] = plan.state_at(i).n_active
        # appends the legacy-shaped record to `metrics` (reg.steps) and
        # folds the fields into the namespaced cumulative metrics
        rec = reg.record_step(i, rec)
        if args.verbose and (i % args.log_every == 0 or i == args.steps - 1):
            log_step(rec)
        if args.ckpt_dir and (i + 1) % args.ckpt_every == 0:
            tree = {"params": params, "opt": opt_state}
            if esd_snap is not None:
                tree["esd"] = esd_snap
            if codec is not None:
                tree["qres"] = qres
            save_checkpoint(args.ckpt_dir, i + 1, tree)
        return rec

    # host batch source, optionally with the lookahead dedup window
    stream = PrefetchLoader(wl.stream(args.seed + 1, k), depth=2)
    if args.lookahead > 0:
        src = iter(LookaheadWindow(stream, args.lookahead,
                                   key=lambda b: b[0]))
    else:
        src = ((item, None) for item in stream)
    # resume: the stream is a pure function of the seed, so skipping the
    # first `start` batches re-aligns it with the interrupted run
    for _ in range(start):
        next(src)

    def device_batches():
        for (sparse, dense, labels), meta in src:
            yield ((jax.device_put(jnp.asarray(sparse), batch_shd(2)),
                    jax.device_put(jnp.asarray(dense), batch_shd(2)),
                    jax.device_put(jnp.asarray(labels), batch_shd(1))), meta)

    if not use_esd:
        dev_batches = device_batches()
        for i in range(start, args.steps):
            try:
                (sparse, dense, labels), meta = next(dev_batches)
            except StopIteration:
                break
            if codec is None:
                params, opt_state, loss = train_jit(params, opt_state,
                                                    sparse, dense, labels)
            else:
                params, opt_state, qres, loss = train_jit_q(
                    params, opt_state, qres, sparse, dense, labels)
            record(i, loss, None, meta, {})
        return metrics

    adv_step = count(start)
    if plan is None:
        pf_plane = (jax.device_put(prefetch_init(args.prefetch_slots,
                                                 cfg.embedding_dim),
                                   replicated)
                    if use_prefetch else None)
        pf_cands = max(8 * args.prefetch, 256)
        dec_step = count(start)

        @jax.jit
        @jax.named_scope("esd.decide")
        def with_staged(state, memb):
            # price the staging plane into Alg. 1: a staged row pulls for
            # free, so the dispatch objective sees it as a cluster-resident
            # latest copy (decision-side view only — the committed cache
            # state never includes it)
            return dataclasses.replace(
                state, latest=state.latest | memb[None, :])

        def decide_fn(state, batch):
            i = next(dec_step)
            if use_prefetch:
                state = with_staged(
                    state, staged_membership(pf_plane, V_space, i))
            return decide_jit(state, batch[0][0])

        def advance_fn(state, batch, assign):
            nonlocal pf_plane
            (s, d, l), meta = batch
            i = next(adv_step)
            aux = {}
            if use_prefetch:
                # split this step's misses against the plane as staged by
                # steps < i, then pull rows for the window's future
                # misses — the pull overlaps step i's training (async
                # dispatch), which is what moves it off the critical path
                memb = staged_membership(pf_plane, V_space, i)
                x, new_state, counts = advance_jit(state, s, d, l, assign,
                                                   memb)
                cids, cexp = prefetch_candidates(meta, i, pf_cands)
                resident = new_state.latest.any(axis=0)
                with get_tracer().span("prefetch.pull", track="prefetch",
                                       step=i):
                    # the pull kernel reads the table on one device; a
                    # table row-sharded over several takes the XLA gather
                    pf_plane, n_pulled = prefetch_step(
                        pf_plane, params["embed"], resident,
                        jnp.asarray(cids), jnp.asarray(cexp), i,
                        budget=args.prefetch, codec=args.codec,
                        use_pallas=n == 1)
                aux["prefetch_pulled"] = n_pulled
            else:
                x, new_state, counts = advance_jit(state, s, d, l, assign)
            esd_seen[i] = new_state
            aux.update({"counts": counts, "meta": meta})
            return x, new_state, aux

        realized_fn = None
        if args.stale_decide or args.decide_ahead:
            realized_fn = lambda state, batch, assign: realized_jit(
                state, batch[0][0], assign)
        repair_fn = None
        if args.decide_ahead:
            repair_jit = make_dlrm_repair_stage(mesh, n, m, t_tran,
                                                part=part,
                                                cap_slack=args.cap_slack)

            def repair_fn(committed, decided, batch, assign):
                a2, n_re = repair_jit(committed, decided, batch[0][0],
                                      assign)
                return a2, {"n_reassigned": n_re}
    else:
        # fold the plan into the per-step stage arrays: effective link
        # times (bandwidth droop / PS outage), cost-column bias
        # (stragglers + finite dead-worker penalty), membership mask.
        # Each stage tracks its own step counter — the pipeline may run
        # decide/advance ahead of train, but every stage sees steps in
        # order, offset by the resume start.
        t_np = np.asarray(t_tran)

        def fault_arrays(i):
            cs = plan.state_at(i)
            t_eff = effective_t(t_np, cs)
            bias = cost_column_bias(t_eff, wl.width, cs.active,
                                    cs.compute_factor, args.compute_time_s)
            return (jnp.asarray(t_eff, t_tran.dtype),
                    jnp.asarray(bias, jnp.float32),
                    jnp.asarray(cs.active))

        dec_step, rea_step = count(start), count(start)

        def decide_fn(state, batch):
            t_arr, bias, act = fault_arrays(next(dec_step))
            return decide_jit(state, batch[0][0], t_arr, bias, act)

        def advance_fn(state, batch, assign):
            (s, d, l), meta = batch
            i = next(adv_step)
            _, _, act = fault_arrays(i)
            x, new_state, counts = advance_jit(state, s, d, l, assign, act)
            esd_seen[i] = new_state
            return x, new_state, {"counts": counts, "meta": meta}

        realized_fn = None
        repair_fn = None
        if args.stale_decide:
            def realized_fn(state, batch, assign):
                t_arr, bias, act = fault_arrays(next(rea_step))
                return realized_jit(state, batch[0][0], assign,
                                    t_arr, bias, act)

    def train_fn(x):
        nonlocal params, opt_state, qres
        if codec is None:
            params, opt_state, loss = train_jit(params, opt_state, *x)
        else:
            params, opt_state, qres, loss = train_jit_q(
                params, opt_state, qres, *x)
        return loss

    runner = PipelinedRunner(
        decide_fn, advance_fn, train_fn, esd,
        depth=args.pipeline_depth, stale=args.stale_decide,
        realized_cost_fn=realized_fn, decide_ahead=args.decide_ahead,
        repair_fn=repair_fn)
    runner.run(device_batches(), steps=args.steps - start,
               record_fn=lambda t, loss, aux, info: record(
                   start + t, loss, aux["counts"], aux["meta"], info,
                   aux.get("prefetch_pulled")))
    return metrics


# --------------------------------------------------------------------------
# LM training
# --------------------------------------------------------------------------
def run_lm(args):
    cfg = get_config(args.arch, smoke=args.smoke)
    mesh = make_host_mesh()
    n_dev = mesh.shape["data"]
    optimizer = get_optimizer("adam", args.lr)
    params = api.init_model(jax.random.key(args.seed), cfg)
    opt_state = optimizer.init(params)
    # single-host run: model axis is 1 wide, so the specs reduce to pure
    # data parallelism — params/opt state replicated, batch data-sharded.
    p_shd = to_shardings(param_specs(params, cfg, model_size=1), mesh)
    o_shd = to_shardings(param_specs(opt_state, cfg, model_size=1), mesh)
    params = jax.device_put(params, p_shd)
    opt_state = jax.device_put(opt_state, o_shd)
    tok_shd = NamedSharding(mesh, P("data", None))

    start = 0
    if args.resume:
        if args.ckpt_dir is None:
            raise SystemExit("--resume needs --ckpt-dir")
        restored, start = restore_checkpoint(
            args.ckpt_dir, {"params": params, "opt": opt_state})
        params = jax.device_put(restored["params"], p_shd)
        opt_state = jax.device_put(restored["opt"], o_shd)
        if args.verbose:
            log_step({"resumed_from_step": start})

    B = max(args.batch_per_worker * n_dev, n_dev)
    S = args.seq_len

    @partial(jax.jit, donate_argnums=(0, 1))
    def step(params, opt_state, tokens, labels):
        loss, grads = jax.value_and_grad(api.train_loss)(
            params, cfg, {"tokens": tokens, "labels": labels}, remat=False)
        params, opt_state = optimizer.update(grads, opt_state, params)
        return params, opt_state, loss

    stream = PrefetchLoader(token_stream(args.seed, cfg.vocab, B, S + 1), depth=2)
    for _ in range(start):
        next(stream)
    reg = MetricsRegistry()
    set_registry(reg)
    metrics = reg.steps
    for i in range(start, args.steps):
        tok = next(stream)
        t0 = time.perf_counter()
        tr = get_tracer()
        with tr.span("train.issue", track="train/0", step=i):
            params, opt_state, loss = step(
                params, opt_state,
                jax.device_put(jnp.asarray(tok[:, :-1]), tok_shd),
                jax.device_put(jnp.asarray(tok[:, 1:]), tok_shd))
        with tr.span("loss.wait", track="train/0", step=i):
            loss = float(loss)
        rec = reg.record_step(i, {"loss": loss,
                                  "wall_s": round(time.perf_counter() - t0,
                                                  4)})
        if args.verbose and (i % args.log_every == 0 or i == args.steps - 1):
            log_step(rec)
        if args.ckpt_dir and (i + 1) % args.ckpt_every == 0:
            save_checkpoint(args.ckpt_dir, i + 1,
                            {"params": params, "opt": opt_state})
    return metrics


def build_parser():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch-per-worker", type=int, default=32)
    ap.add_argument("--seq-len", type=int, default=64)
    ap.add_argument("--lr", type=float, default=1e-2)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced (CPU-sized) arch variant")
    ap.add_argument("--esd-alpha", type=float, default=None,
                    help="enable ESD dispatch with this HybridDis alpha")
    ap.add_argument("--esd-engine", choices=("sparse", "dense"),
                    default="sparse",
                    help="touched-ids (sparse) or full-plane (dense) "
                         "cost/cache engine")
    ap.add_argument("--exchange", choices=("padded", "ragged"),
                    default="padded",
                    help="sample wire path: fixed m/n all_to_all (padded) "
                         "or the repro.exchange budgeted executor (ragged; "
                         "bitwise-equal under the hard m/n capacity)")
    ap.add_argument("--cap-slack", type=float, default=0.0,
                    help="relax the per-worker dispatch capacity by this "
                         "fraction of m/n (needs --exchange ragged; workers "
                         "then train uneven PAD-masked batches)")
    ap.add_argument("--pipeline-depth", type=int, default=1,
                    help="decide/advance stages may run this many steps "
                         "ahead of training (1 = synchronous, bitwise-equal "
                         "to the pipelined schedule; 2 hides the dispatch "
                         "decision under the previous step's fwd/bwd)")
    ap.add_argument("--lookahead", type=int, default=0,
                    help="W-batch dedup window over the input stream "
                         "(repro.pipeline.window); logs per-step "
                         "window_dedup_frac")
    ap.add_argument("--decide-ahead", type=int, default=0,
                    help="buffer up to this many + 1 dispatch decisions, "
                         "each made on the newest committed state at its "
                         "decide time (progressively stale; bounded by the "
                         "chained staleness bound) — sustains pipeline "
                         "depth > 2; a commit-time repair re-places only "
                         "the samples whose ids changed state "
                         "(n_reassigned), and alg1_realized re-scores on "
                         "the committed state")
    ap.add_argument("--prefetch", type=int, default=0,
                    help="stage up to this many future-miss rows per step "
                         "from the PS tier into the window-driven staging "
                         "plane (needs --lookahead > 0); misses then split "
                         "into prefetch hits (wire cost hidden under "
                         "training) vs demand misses in the per-step "
                         "metrics (0 = off, bitwise-identical path)")
    ap.add_argument("--prefetch-slots", type=int, default=512,
                    help="staging-plane capacity in rows")
    ap.add_argument("--stale-decide", action="store_true",
                    help="decide on the t-1 cache state (double-buffered) "
                         "so the decision overlaps even the cache update; "
                         "logs the commit-time re-score alg1_realized "
                         "(needs --pipeline-depth >= 2)")
    ap.add_argument("--capacity-ratio", type=float, default=0.2)
    ap.add_argument("--n-ps", type=int, default=1,
                    help="partition the embedding V-space over this many "
                         "parameter servers (repro.ps)")
    ap.add_argument("--ps-layout", choices=("contiguous", "hashed"),
                    default="contiguous")
    ap.add_argument("--ps-hetero", action="store_true",
                    help="heterogeneous PS links: last PS 0.5 Gbps, rest "
                         "5 Gbps (needs --n-ps > 1)")
    ap.add_argument("--fault-plan", default=None,
                    help="repro.elastic fault schedule: compact DSL (e.g. "
                         "'crash@3:1g; rejoin@6:1w; straggle@2:0x4-10') or "
                         "@file.json; needs ESD + --exchange ragged")
    ap.add_argument("--compute-time-s", type=float, default=0.010,
                    help="nominal per-step compute time; prices straggler "
                         "slowdown into the dispatch cost bias")
    ap.add_argument("--codec", default=None,
                    help="wire codec for embedding traffic: none (exact "
                         "fp32), fp16, int8, int4, optionally with a "
                         "quantization block like int8:32 (default: none)")
    ap.add_argument("--codec-policy", choices=("uniform", "bandwidth"),
                    default="uniform",
                    help="uniform: every link uses --codec; bandwidth: "
                         "links at/above the median bandwidth get fp16, "
                         "slower links get --codec (priced into the "
                         "dispatch cost)")
    ap.add_argument("--ckpt-dir", type=Path, default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--resume", action="store_true",
                    help="restore the newest checkpoint in --ckpt-dir "
                         "(params, optimizer, ESD dispatch state) and "
                         "continue from its step")
    ap.add_argument("--log-every", type=int, default=5)
    ap.add_argument("--verbose", action="store_true", default=True)
    ap.add_argument("--trace-out", type=Path, default=None,
                    help="export a Chrome/Perfetto trace_event JSON of "
                         "the run's spans (decide/advance/train/prefetch/"
                         "loader/io tracks) to this path; open it in "
                         "chrome://tracing or ui.perfetto.dev")
    ap.add_argument("--trace-buffer", type=int, default=65536,
                    help="tracer ring-buffer capacity in spans "
                         "(drop-oldest)")
    return ap


def main(argv=None):
    args = build_parser().parse_args(argv)
    use_compile_cache()
    trace = args.trace_out is not None
    tracer = Tracer(capacity=args.trace_buffer) if trace else None
    prev = set_tracer(tracer) if trace else None
    try:
        if args.arch in DLRM_CONFIGS:
            metrics = run_dlrm(args)
        else:
            metrics = run_lm(args)
    finally:
        if trace:
            set_tracer(prev)
            tracer.export(args.trace_out)
    if trace:
        if tracer.dropped:
            print(f"trace ring dropped {tracer.dropped} oldest spans "
                  f"(--trace-buffer {args.trace_buffer})", file=sys.stderr)
        if args.verbose:
            print("== top spans by total wall time ==", file=sys.stderr)
            for row in tracer.durations(10):
                print(f"  {row['name']:<22} n={row['count']:<6} "
                      f"total={row['total_s']:.4f}s "
                      f"mean={row['mean_s'] * 1e3:.3f}ms "
                      f"max={row['max_s'] * 1e3:.3f}ms", file=sys.stderr)
    return metrics


if __name__ == "__main__":
    main()
