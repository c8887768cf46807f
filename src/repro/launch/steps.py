"""Step builders: jitted train_step / serve_step factories + ShapeDtypeStruct
input specs for the dry-run (no allocation, weak-type-correct)."""
from __future__ import annotations

from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from ..configs.base import INPUT_SHAPES, ModelConfig, ShapeConfig
from ..models import api
from ..optim import Optimizer, get_optimizer


# Hillclimb hook: when set (e.g. jnp.bfloat16), gradients are cast before
# the optimizer so the data-parallel sync happens in half precision
# (standard mixed-precision practice — §Perf hillclimb 3).
GRAD_DTYPE = None


def make_train_step(cfg: ModelConfig, optimizer: Optimizer, remat: bool = True,
                    grad_dtype=None):
    def train_step(params, opt_state, batch):
        nonlocal grad_dtype
        grad_dtype = grad_dtype or GRAD_DTYPE
        loss, grads = jax.value_and_grad(api.train_loss)(
            params, cfg, batch, remat=remat
        )
        if grad_dtype is not None:
            grads = jax.tree.map(lambda g: g.astype(grad_dtype), grads)
        new_params, new_opt = optimizer.update(grads, opt_state, params)
        return new_params, new_opt, loss

    return train_step


def make_serve_step(cfg: ModelConfig):
    def serve_step(params, cache, token, pos):
        return api.decode_step(params, cfg, token, cache, pos)

    return serve_step


def make_esd_exchange(mode: str, n: int, m: int, axis_name: str = "data",
                      use_pallas: bool = False, budget: int | None = None,
                      out_rows: int | None = None, codec=None):
    """Row-exchange function for the DLRM ESD step (inside shard_map):
    routes any (m, ...) per-sample array (aux features, labels) to the
    worker its sample was assigned to.

    ``mode="padded"`` is the fixed m/n all_to_all baseline;
    ``mode="ragged"`` runs the repro.exchange executor — with the
    default ``budget = m // n`` / ``out_rows = m`` it is bitwise-equal
    to the padded path (the dispatch capacity is the hard m/n split);
    with a relaxed capacity (``cap_slack > 0``) pass the matching
    ``exchange_budget`` and ``out_rows = n * budget`` so aux rows ride
    the same wire layout as the samples (PAD fill = -1 past the valid
    prefix).

    ``route(a, assign)`` returns ``(out, overflow)``; overflow is the
    cluster-total rows an undersized ragged budget could not ship
    (always 0 on the padded path, whose shape admits no overflow).

    ``codec`` (ragged only) quantizes FLOAT payloads on the wire via
    :func:`repro.exchange.ragged.ragged_exchange_quant`; integer rows
    (sample ids, labels) always travel exact — codes must not be lossy.
    """
    if mode not in ("padded", "ragged"):
        raise ValueError(f"unknown exchange mode {mode!r}")
    if codec is not None and mode != "ragged":
        raise ValueError("codec exchange needs mode='ragged'")
    if mode == "padded":
        if budget not in (None, m // n) or out_rows not in (None, m):
            raise ValueError("padded exchange is fixed-shape: budget/out_rows "
                             "cannot deviate from m/n and m")

        def route(a, assign):
            order = jnp.argsort(assign, stable=True)
            routed = a[order].reshape((n, m // n) + a.shape[1:])
            out = jax.lax.all_to_all(routed, axis_name, 0, 0).reshape(
                (m,) + a.shape[1:])
            return out, jnp.zeros((), jnp.int32)
    else:
        from ..exchange.ragged import ragged_exchange, ragged_exchange_quant
        from ..quant.codecs import get_codec
        codec = get_codec(codec)
        budget = m // n if budget is None else budget
        out_rows = m if out_rows is None else out_rows

        def route(a, assign):
            if (codec is not None and a.ndim == 2
                    and jnp.issubdtype(a.dtype, jnp.floating)):
                out, _, _, overflow = ragged_exchange_quant(
                    a, assign, axis_name, budget, codec, out_rows=out_rows,
                    use_pallas=use_pallas)
            else:
                out, _, _, overflow = ragged_exchange(
                    a, assign, axis_name, budget, out_rows=out_rows,
                    use_pallas=use_pallas)
            return out, overflow

    return route


def raise_on_overflow(counts: dict) -> None:
    """Host-side guard for the ragged wire: an undersized budget DROPS
    rows inside jit (no aborts in a collective), so drivers must check
    the step's ``exchange_overflow`` counter once it is concrete and
    fail loudly instead of training on a truncated batch."""
    ov = counts.get("exchange_overflow")
    if ov is None:
        return
    ov = int(np.asarray(ov))
    if ov:
        raise RuntimeError(
            f"ragged exchange dropped {ov} rows: the per-link budget is "
            f"smaller than the dispatch capacity (raise cap_slack's budget "
            f"or fix the assignment)")


def make_dlrm_train_jit(cfg, optimizer: Optimizer, loss_fn, part=None):
    """The jitted DLRM train step

      train_jit(params, opt_state, sparse, dense, labels)
          -> (params, opt_state, loss)

    with the parameters and optimizer state donated.  ``part`` (plain
    training on a multi-PS table) maps raw ids into the PS-linearized
    space first.  Its device time carries stable scope names:
    ``dlrm.train_step`` over ``dlrm.forward`` (the loss; its transposes
    are the backward) and ``optim.update``.
    """
    forward = jax.named_scope("dlrm.forward")(loss_fn)

    @partial(jax.jit, donate_argnums=(0, 1))
    @jax.named_scope("dlrm.train_step")
    def train_jit(params, opt_state, sparse, dense, labels):
        if part is not None:
            sparse = part.to_linear(sparse)
        loss, grads = jax.value_and_grad(forward)(params, cfg, sparse, dense,
                                                  labels)
        with jax.named_scope("optim.update"):
            params, opt_state = optimizer.update(grads, opt_state, params)
        return params, opt_state, loss

    return train_jit


def make_dlrm_esd_stages(mesh, n: int, m: int, V_space: int, t_tran,
                         alpha: float, *, part=None, exchange: str = "padded",
                         cap_slack: float = 0.0, sparse_esd: bool = True,
                         capacity: int | None = None,
                         use_pallas: bool = False, elastic: bool = False,
                         max_failures: int = 0, codec=None):
    """Jitted stage functions for the pipelined DLRM ESD step
    (repro.pipeline.runner): the per-step work splits into

      decide(esd_state, sparse)                    -> (assign (k,), alg1)
      advance(esd_state, sparse, dense, labels, assign)
          -> ((sparse', dense', labels'), new_esd_state, counts)
      realized_cost(esd_state, sparse, assign)     -> alg1 scalar

    ``decide`` is Alg. 1 + hybrid assignment per shard (the stage the
    pipeline hides under training); ``advance`` moves the samples over
    the selected wire path and runs the cache-state machine; neither
    reads the model parameters, so the chain can run ahead of the train
    stage.  ``realized_cost`` re-scores an assignment under a given
    state — the stale mode's commit-time correction.

    With ``cap_slack > 0`` (needs ``exchange="ragged"``) the assignment
    may skew past m/n and the exchanged arrays come back with
    ``out_rows = n * exchange_budget(cap, m)`` rows per shard, valid
    rows compacted first and PAD (-1) after — pair with the PAD-masked
    DLRM loss.  Returns ``(decide, advance, realized_cost, out_rows)``.

    ``elastic=True`` (repro.elastic, needs ``exchange="ragged"``) builds
    churn-tolerant stages whose signatures take three extra *array*
    arguments — per-step values, never shapes, so membership churn costs
    zero recompiles after warmup:

      decide(esd_state, sparse, t_arr, col_bias, active)
      advance(esd_state, sparse, dense, labels, assign, active)
      realized_cost(esd_state, sparse, assign, t_arr, col_bias, active)

    ``t_arr`` is the step's effective link times (bandwidth droop /
    PS outage folded in), ``col_bias`` the per-worker cost bias
    (straggler excess compute; finite dead-worker penalty), ``active``
    the membership mask (masks dead workers' state rows in decide AND
    before the cache update, so their stale planes never feed phase A —
    a rejoin is cold).  The static dispatch capacity is raised to
    ``ceil(m / (n - max_failures))`` so the survivors of the worst
    planned simultaneous loss can absorb every sample; a dead worker's
    exchanged block comes back all-PAD (pair with the PAD-masked loss).
    With neutral arrays (all active, zero bias, nominal t) the outputs
    are bitwise-equal to the non-elastic ragged stages.
    """
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from ..core.dispatch_tpu import (dispatch_cap, esd_cost_matrix,
                                     esd_decide, esd_state_update,
                                     esd_state_update_sparse, exchange_budget,
                                     need_ids_list, need_matrix)

    axis = "data"
    if cap_slack > 0.0 and exchange != "ragged":
        # same guard esd_dispatch enforces: a relaxed cap can assign a
        # worker more than m/n samples, which the fixed-shape padded
        # route would silently deliver to the wrong workers
        raise ValueError("cap_slack > 0 needs exchange='ragged' (the padded "
                         "all_to_all requires equal m/n groups)")
    cap = dispatch_cap(m, n, cap_slack)
    if elastic:
        if exchange != "ragged":
            raise ValueError("elastic stages need exchange='ragged' (a dead "
                             "worker breaks the padded equal-groups "
                             "all_to_all)")
        if not 0 <= max_failures < n:
            raise ValueError(f"max_failures {max_failures} outside [0, {n})")
        # survivors of the worst planned loss must absorb every sample
        cap = max(cap, -(-m // (n - max_failures)))
        budget = m // n if cap == m // n else exchange_budget(cap, m)
        out_rows = m if cap == m // n else n * budget
    else:
        budget = m // n if cap_slack <= 0.0 else exchange_budget(cap, m)
        out_rows = m if cap_slack <= 0.0 else n * budget
    if codec is not None and exchange != "ragged":
        raise ValueError("codec exchange needs exchange='ragged'")
    if exchange == "ragged":
        route = make_esd_exchange(exchange, n, m, use_pallas=use_pallas,
                                  budget=budget, out_rows=out_rows,
                                  codec=codec)
    else:
        route = make_esd_exchange(exchange, n, m, use_pallas=use_pallas)

    def decide_shard(state, s):
        if part is not None:
            s = part.to_linear(s)
        assign, alg1 = esd_decide(s, state, t_tran, alpha, axis_name=axis,
                                  use_pallas=use_pallas, part=part,
                                  cap_slack=cap_slack, with_cost=True)
        return assign, jax.lax.psum(alg1, axis)

    @jax.jit
    @jax.named_scope("esd.decide")
    def decide(esd_state, sparse):
        return shard_map(
            lambda s: decide_shard(esd_state, s), mesh=mesh,
            in_specs=(P(axis, None),), out_specs=(P(axis), P()),
            check_vma=False)(sparse)

    def advance_shard(s, d, l, a):
        if part is not None:
            s = part.to_linear(s)
        # every array rides the same assignment/budget, so one route's
        # (psummed) overflow counter covers the step
        s2, overflow = route(s, a)
        d2, _ = route(d, a)
        l2, _ = route(l, a)
        need = (need_ids_list(s2, axis) if sparse_esd
                else need_matrix(s2, axis, V_space))
        return s2, d2, l2, need, overflow

    def exchange_and_need(sparse, dense, labels, assign):
        with jax.named_scope("esd.exchange"):
            return shard_map(
                advance_shard, mesh=mesh,
                in_specs=(P(axis, None), P(axis, None), P(axis), P(axis)),
                out_specs=(P(axis, None), P(axis, None), P(axis),
                           P(None, None), P()),
                check_vma=False)(sparse, dense, labels, assign)

    @jax.jit
    @jax.named_scope("esd.advance")
    def advance(esd_state, sparse, dense, labels, assign, staged=None):
        # staged: optional (V,) bool prefetch-plane membership — splits
        # the step's miss count into prefetch hits vs demand misses
        # (pure accounting; None leaves the update bitwise unchanged)
        s2, d2, l2, need, overflow = exchange_and_need(sparse, dense, labels,
                                                       assign)
        with jax.named_scope("esd.cache_update"):
            if sparse_esd:
                new_state, counts = esd_state_update_sparse(
                    esd_state, need, capacity, part, staged=staged)
            else:
                new_state, counts = esd_state_update(esd_state, need,
                                                     capacity, staged=staged)
        counts = dict(counts)
        counts["exchange_overflow"] = overflow
        return (s2, d2, l2), new_state, counts

    def realized_shard(state, s, a):
        if part is not None:
            s = part.to_linear(s)
        C = esd_cost_matrix(s, state, t_tran, use_pallas=use_pallas,
                            part=part)
        alg1 = jnp.take_along_axis(C, a[:, None], axis=1)[:, 0].sum()
        return jax.lax.psum(alg1, axis)

    @jax.jit
    def realized_cost(esd_state, sparse, assign):
        return shard_map(
            lambda s, a: realized_shard(esd_state, s, a), mesh=mesh,
            in_specs=(P(axis, None), P(axis)), out_specs=P(),
            check_vma=False)(sparse, assign)

    if not elastic:
        return decide, advance, realized_cost, out_rows

    # -- elastic variants: per-step churn arrays, static shapes ------------
    from ..elastic import mask_state

    def decide_shard_e(state, s, t_arr, col_bias):
        if part is not None:
            s = part.to_linear(s)
        assign, alg1 = esd_decide(s, state, t_arr, alpha, axis_name=axis,
                                  use_pallas=use_pallas, part=part,
                                  cap_slack=cap_slack, with_cost=True,
                                  col_bias=col_bias, cap=cap)
        return assign, jax.lax.psum(alg1, axis)

    @jax.jit
    @jax.named_scope("esd.decide")
    def decide_e(esd_state, sparse, t_arr, col_bias, active):
        state = mask_state(esd_state, active)
        return shard_map(
            lambda s: decide_shard_e(state, s, t_arr, col_bias), mesh=mesh,
            in_specs=(P(axis, None),), out_specs=(P(axis), P()),
            check_vma=False)(sparse)

    @jax.jit
    @jax.named_scope("esd.advance")
    def advance_e(esd_state, sparse, dense, labels, assign, active):
        s2, d2, l2, need, overflow = exchange_and_need(sparse, dense, labels,
                                                       assign)
        with jax.named_scope("esd.cache_update"):
            # mask BEFORE the update: a dead worker's stale planes must
            # not survive into the committed state (its rejoin is cold)
            state = mask_state(esd_state, active)
            if sparse_esd:
                new_state, counts = esd_state_update_sparse(state, need,
                                                            capacity, part)
            else:
                new_state, counts = esd_state_update(state, need, capacity)
        counts = dict(counts)
        counts["exchange_overflow"] = overflow
        return (s2, d2, l2), new_state, counts

    def realized_shard_e(state, s, a, t_arr, col_bias):
        if part is not None:
            s = part.to_linear(s)
        C = esd_cost_matrix(s, state, t_arr, use_pallas=use_pallas,
                            part=part, col_bias=col_bias)
        alg1 = jnp.take_along_axis(C, a[:, None], axis=1)[:, 0].sum()
        return jax.lax.psum(alg1, axis)

    @jax.jit
    def realized_cost_e(esd_state, sparse, assign, t_arr, col_bias, active):
        state = mask_state(esd_state, active)
        return shard_map(
            lambda s, a: realized_shard_e(state, s, a, t_arr, col_bias),
            mesh=mesh, in_specs=(P(axis, None), P(axis)), out_specs=P(),
            check_vma=False)(sparse, assign)

    return decide_e, advance_e, realized_cost_e, out_rows


def make_dlrm_repair_stage(mesh, n: int, m: int, t_tran, *, part=None,
                           cap_slack: float = 0.0, use_pallas: bool = False):
    """Jitted commit-time repair for the decide-ahead chain
    (``PipelinedRunner(repair_fn=...)``):

      repair(committed_state, decide_state, sparse, assign)
          -> (assign', n_reassigned)

    Flags exactly the samples whose ids' state columns (``latest`` /
    ``dirty`` — the planes the Alg.-1 cost reads) changed between the
    decide-time state and the committed one, and re-places only those
    via the capacity-capped greedy (``esd_reassign``) against the
    committed-state cost matrix.  Unflagged samples keep their stale
    assignment, which is still exact: their cost columns are untouched,
    so the original argmin stands.  Much cheaper than a full re-decide
    and runs at commit, off the decide stream.
    """
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from ..core.dispatch_tpu import (changed_samples_mask, dispatch_cap,
                                     esd_cost_matrix, esd_reassign)

    axis = "data"

    def repair_shard(committed, decided, s, a):
        if part is not None:
            s = part.to_linear(s)
        flagged = changed_samples_mask(s, decided, committed)
        C = esd_cost_matrix(s, committed, t_tran, use_pallas=use_pallas,
                            part=part)
        cap = dispatch_cap(s.shape[0], n, cap_slack)
        a2, n_re = esd_reassign(C, a, flagged, cap)
        return a2, jax.lax.psum(n_re, axis)

    @jax.jit
    def repair(committed_state, decide_state, sparse, assign):
        return shard_map(
            lambda s, a: repair_shard(committed_state, decide_state, s, a),
            mesh=mesh, in_specs=(P(axis, None), P(axis)),
            out_specs=(P(axis), P()), check_vma=False)(sparse, assign)

    return repair


# --------------------------------------------------------------------------
# abstract input specs (dry-run)
# --------------------------------------------------------------------------
def _sds(tree):
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), tree
    )


def param_shapes(cfg: ModelConfig):
    return _sds(jax.eval_shape(partial(api.init_model, cfg=cfg),
                               jax.random.key(0)))


def opt_state_shapes(cfg: ModelConfig, optimizer: Optimizer):
    p = param_shapes(cfg)
    return _sds(jax.eval_shape(optimizer.init, p))


def batch_shapes(cfg: ModelConfig, shape: ShapeConfig):
    """ShapeDtypeStructs for every model input (train batch)."""
    B, S = shape.global_batch, shape.seq_len
    if cfg.family == "audio":
        dec = min(S, 448)
        return {
            "frames": jax.ShapeDtypeStruct((B, S, cfg.d_model), jnp.bfloat16),
            "tokens": jax.ShapeDtypeStruct((B, dec), jnp.int32),
            "labels": jax.ShapeDtypeStruct((B, dec), jnp.int32),
        }
    if cfg.family == "vlm":
        return {
            "tokens": jax.ShapeDtypeStruct((B, S - cfg.n_patches), jnp.int32),
            "labels": jax.ShapeDtypeStruct((B, S - cfg.n_patches), jnp.int32),
            "patches": jax.ShapeDtypeStruct((B, cfg.n_patches, cfg.d_model),
                                            jnp.bfloat16),
        }
    return {
        "tokens": jax.ShapeDtypeStruct((B, S), jnp.int32),
        "labels": jax.ShapeDtypeStruct((B, S), jnp.int32),
    }


def cache_shapes(cfg: ModelConfig, shape: ShapeConfig):
    return _sds(jax.eval_shape(
        partial(api.init_decode_cache, cfg, shape.global_batch, shape.seq_len)
    ))


def decode_input_shapes(cfg: ModelConfig, shape: ShapeConfig):
    B = shape.global_batch
    return (
        jax.ShapeDtypeStruct((B, 1), jnp.int32),     # token
        jax.ShapeDtypeStruct((), jnp.int32),         # pos
    )


def input_specs(arch_cfg: ModelConfig, shape_name: str, optimizer_name: str = "adam"):
    """Everything the dry-run needs to lower one (arch, shape) combo."""
    shape = INPUT_SHAPES[shape_name]
    opt = get_optimizer(optimizer_name, 1e-3)
    out: dict[str, Any] = {"shape": shape, "optimizer": opt,
                           "params": param_shapes(arch_cfg)}
    if shape.kind == "train":
        out["opt_state"] = opt_state_shapes(arch_cfg, opt)
        out["batch"] = batch_shapes(arch_cfg, shape)
    elif shape.kind == "prefill":
        out["batch"] = batch_shapes(arch_cfg, shape)
    else:  # decode
        out["cache"] = cache_shapes(arch_cfg, shape)
        out["token"], out["pos"] = decode_input_shapes(arch_cfg, shape)
    return out


def make_prefill_step(cfg: ModelConfig, remat: bool = True):
    """Forward-only logits for the prefill shape (inference)."""
    def prefill_step(params, batch):
        if cfg.family == "audio":
            from ..models import whisper
            memory = whisper.encode(params, cfg, batch["frames"], remat=remat)
            return whisper.decode_train(params, cfg, batch["tokens"], memory,
                                        remat=remat)
        from ..models import backbone
        logits, _ = backbone.forward(
            params, cfg, batch["tokens"],
            prefix_embeds=batch.get("patches"), remat=remat,
        )
        return logits

    return prefill_step
