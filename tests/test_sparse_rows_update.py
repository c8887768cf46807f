"""The DLRM train step updates its tables on the rows a step touches.

``make_dlrm_train_jit`` gathers the step's distinct rows, trains them as
small leaves and scatters them back.  Under row-wise Adagrad and SGD that
is the dense update of every row, written out here as the reference: two
steps on the same inputs, then every row no batch touched is bitwise as
the seed made it, in the tables and in their accumulators, and the rest
matches to float32 rounding.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import DLRM_CONFIGS
from repro.data.synthetic import WORKLOADS
from repro.launch.steps import make_dlrm_train_jit
from repro.models import dlrm
from repro.optim import get_optimizer
from repro.ps import make_partition

B = 8


def _dense_step(cfg, optimizer, part):
    """The update of every row: the gradient of the whole tables."""
    @jax.jit
    def step(params, opt_state, sparse, dense, labels):
        if part is not None:
            sparse = part.to_linear(sparse)
        loss, grads = jax.value_and_grad(dlrm.bce_loss)(params, cfg, sparse,
                                                        dense, labels)
        params, opt_state = optimizer.update(grads, opt_state, params)
        return params, opt_state, loss

    return step


def _batches(wl, V, batch, rng):
    """Two batches: "mixed" draws from the stream, then makes
    id 0 appear twice and one sample's last slots PAD; "distinct" fills
    every slot with another id (no PAD, so no fill slot is left)."""
    out = []
    for _ in range(2):
        if batch == "distinct":
            sparse = rng.permutation(V)[:B * wl.width].reshape(B, wl.width)
        else:
            sparse = wl.sample_batch(rng, B)
            sparse[0, 0] = sparse[1, 0] = 0
            sparse[2, -3:] = -1
        out.append((jnp.asarray(sparse, jnp.int32),
                    jnp.asarray(wl.dense_batch(rng, B)),
                    jnp.asarray(wl.label_batch(rng, B))))
    return out


def _rows(x, part):
    """A table leaf (or its accumulator) by raw id: a PS stack's rows
    taken back out of the PS-linearized layout."""
    x = np.asarray(x)
    if part is None:
        return x
    flat = x.reshape((part.linear_size,) + x.shape[2:])
    return flat[np.asarray(part.to_linear(np.arange(part.vocab)))]


@pytest.mark.parametrize("arch,n_ps,opt_name,batch", [
    ("wdl-tiny", 1, "rowwise_adagrad", "mixed"),
    ("dcn-tiny", 1, "rowwise_adagrad", "mixed"),
    ("dcnv2-tiny", 1, "rowwise_adagrad", "mixed"),
    ("wdl-tiny", 2, "rowwise_adagrad", "mixed"),
    ("dcn-tiny", 1, "rowwise_adagrad", "distinct"),
    ("wdl-tiny", 1, "sgd", "mixed"),
])
def test_row_update_equals_dense_update(arch, n_ps, opt_name, batch):
    cfg = DLRM_CONFIGS[arch]
    wl = WORKLOADS[cfg.workload]
    V = wl.vocab
    optimizer = get_optimizer(opt_name, 1e-2)
    part = make_partition(V, n_ps, "hashed") if n_ps > 1 else None
    params = dlrm.init_params(jax.random.key(0), cfg, wl)
    if part is not None:
        params = dlrm.ps_stack_tables(params, part)
    state = optimizer.init(params)
    batches = _batches(wl, V, batch, np.random.default_rng(1))

    train_jit = make_dlrm_train_jit(cfg, optimizer, dlrm.bce_loss, part=part)
    dense_step = _dense_step(cfg, optimizer, part)
    got = (jax.tree.map(jnp.copy, params), jax.tree.map(jnp.copy, state))
    want = (params, state)
    for x in batches:
        *got, got_loss = train_jit(*got, *x)
        *want, want_loss = dense_step(*want, *x)
        np.testing.assert_allclose(got_loss, want_loss, rtol=1e-6)

    ids = np.concatenate([np.asarray(x[0]).ravel() for x in batches])
    touched = np.zeros(V, bool)
    touched[ids[ids >= 0]] = True
    assert touched.sum() < V
    tables = [k for k in dlrm.TABLE_LEAVES if k in params]
    seed = (params, state)
    for which, tree in enumerate(got):
        for k, leaf in tree.items() if tree else ():
            if k not in tables:
                jax.tree.map(lambda a, b: np.testing.assert_allclose(
                    a, b, rtol=1e-6), leaf, want[which][k])
                continue
            g = _rows(leaf, part)
            w = _rows(want[which][k], part)
            s = _rows(seed[which][k], part)
            np.testing.assert_array_equal(g[~touched], s[~touched])
            np.testing.assert_array_equal(w[~touched], s[~touched])
            np.testing.assert_allclose(g[touched], w[touched], rtol=1e-6)
            assert not np.array_equal(g[touched], s[touched])


def test_train_step_refuses_adam():
    cfg = DLRM_CONFIGS["wdl-tiny"]
    with pytest.raises(ValueError, match="row-separable"):
        make_dlrm_train_jit(cfg, get_optimizer("adam", 1e-3), dlrm.bce_loss)
