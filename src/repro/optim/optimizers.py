"""Functional optimizers: SGD, Adam, row-wise Adagrad (embedding standard).

Minimal optax-style (init/update) pairs without the dependency.  Row-wise
Adagrad keeps ONE accumulator scalar per embedding row (the standard DLRM
memory trade-off) and is what the paper-style DLRM training uses for its
tables; Adam drives the LLM examples; SGD backs the consistency proof
tests (paper Eq. 1-2 assumes SGD).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax
import jax.numpy as jnp

__all__ = ["sgd", "adam", "rowwise_adagrad", "Optimizer"]


@dataclasses.dataclass(frozen=True)
class Optimizer:
    init: Callable[[Any], Any]
    update: Callable[[Any, Any, Any], tuple[Any, Any]]  # (grads, state, params)
    # The update may run on a table's touched rows alone: it is
    # row-separable, a row whose gradient is zero keeps its value and its
    # state exactly, and the state is empty or mirrors the parameters leaf
    # for leaf with one entry per row (repro.launch.steps.make_dlrm_train_jit)
    row_sparse: bool = False


def sgd(lr: float = 1e-2) -> Optimizer:
    def init(params):
        return ()

    def update(grads, state, params):
        new = jax.tree.map(lambda p, g: (p.astype(jnp.float32)
                                         - lr * g.astype(jnp.float32)).astype(p.dtype),
                           params, grads)
        return new, state

    return Optimizer(init, update, row_sparse=True)


def adam(lr: float = 3e-4, b1: float = 0.9, b2: float = 0.95,
         eps: float = 1e-8) -> Optimizer:
    def init(params):
        zeros = lambda p: jnp.zeros(p.shape, jnp.float32)
        return {
            "mu": jax.tree.map(zeros, params),
            "nu": jax.tree.map(zeros, params),
            "t": jnp.zeros((), jnp.int32),
        }

    def update(grads, state, params):
        t = state["t"] + 1
        mu = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g.astype(jnp.float32),
                          state["mu"], grads)
        nu = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * jnp.square(g.astype(jnp.float32)),
                          state["nu"], grads)
        c1 = 1 - b1 ** t.astype(jnp.float32)
        c2 = 1 - b2 ** t.astype(jnp.float32)

        def step(p, m, v):
            upd = (m / c1) / (jnp.sqrt(v / c2) + eps)
            return (p.astype(jnp.float32) - lr * upd).astype(p.dtype)

        new = jax.tree.map(step, params, mu, nu)
        return new, {"mu": mu, "nu": nu, "t": t}

    return Optimizer(init, update)


def rowwise_adagrad(lr: float = 1e-2, eps: float = 1e-10) -> Optimizer:
    """One accumulator per row for >=2D params, per-element for 1D.

    A "row" is everything but the trailing (embedding) dim, so a
    PS-stacked (n_ps, max_rows, E) table gets per-(shard, local_row)
    accumulators — identical to rank-2 behavior for ordinary (V, E).

    The update is row-separable (a row's step reads only its own gradient
    and accumulator), and a row whose gradient is zero is left exactly as
    it was: its accumulator gains ``mean(0**2) = 0`` and its value loses
    ``lr * 0 * rsqrt(a + eps)``, which is 0 because ``eps > 0`` keeps the
    rsqrt finite.  So updating only the rows a step touched gives the
    same result as updating every row (``row_sparse``).  The accumulator
    adds the mean square of a row's *summed* gradient, so duplicate ids
    of a batch must be summed before the update, not squared one by
    one."""

    def init(params):
        def acc(p):
            if p.ndim >= 2:
                return jnp.zeros(p.shape[:-1], jnp.float32)
            return jnp.zeros(p.shape, jnp.float32)
        return jax.tree.map(acc, params)

    def update(grads, state, params):
        def step(p, g, a):
            g = g.astype(jnp.float32)
            if p.ndim >= 2:
                a_new = a + jnp.mean(jnp.square(g), axis=-1)
                scale = jax.lax.rsqrt(a_new + eps)
                upd = g * scale[..., None]
            else:
                a_new = a + jnp.square(g)
                upd = g * jax.lax.rsqrt(a_new + eps)
            return (p.astype(jnp.float32) - lr * upd).astype(p.dtype), a_new

        flat_p, tdef = jax.tree.flatten(params)
        flat_g = tdef.flatten_up_to(grads)
        flat_a = tdef.flatten_up_to(state)
        out = [step(p, g, a) for p, g, a in zip(flat_p, flat_g, flat_a)]
        new = tdef.unflatten([o[0] for o in out])
        accs = tdef.unflatten([o[1] for o in out])
        return new, accs

    return Optimizer(init, update, row_sparse=True)


def get_optimizer(name: str, lr: float) -> Optimizer:
    return {"sgd": sgd, "adam": adam, "rowwise_adagrad": rowwise_adagrad}[name](lr)
