"""The jitted stages carry stable ``jax.named_scope`` names in the
``op_name`` of every compiled operation.  The benchmark's per-layer
metrics read device time by these names from the trace (its
``bench/scopes.py``), so a rename shows here first."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AxisType

from repro.configs import DLRM_CONFIGS
from repro.core.dispatch_tpu import esd_sparse_init
from repro.core.simulator import DEFAULT_BANDWIDTHS
from repro.data.synthetic import WORKLOADS
from repro.launch.steps import make_dlrm_esd_stages, make_dlrm_train_jit
from repro.models import dlrm
from repro.optim import get_optimizer


@pytest.fixture(scope="module")
def compiled():
    """HLO text of the wdl-tiny stages as the train driver builds them:
    ragged exchange, capacity 0.2 V, prefetch membership priced in."""
    cfg = DLRM_CONFIGS["wdl-tiny"]
    wl = WORKLOADS[cfg.workload]
    mesh = jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(AxisType.Auto, AxisType.Auto))
    n, m, V = 1, 16, wl.vocab
    capacity = int(0.2 * V)
    t_tran = jnp.asarray((cfg.embedding_dim * 4.0) / DEFAULT_BANDWIDTHS(n),
                         jnp.float32)
    decide, advance, _, out_rows = make_dlrm_esd_stages(
        mesh, n, m, V, t_tran, 1.0, exchange="ragged", capacity=capacity)
    esd = esd_sparse_init(n, V, capacity, max_ids=out_rows * wl.width)
    rng = np.random.default_rng(0)
    sparse = jnp.asarray(wl.sample_batch(rng, m), jnp.int32)
    dense = jnp.asarray(wl.dense_batch(rng, m))
    labels = jnp.asarray(wl.label_batch(rng, m))
    assign = jnp.zeros((m,), jnp.int32)
    optimizer = get_optimizer("rowwise_adagrad", 1e-2)
    params = dlrm.init_params(jax.random.key(0), cfg, wl)
    train_jit = make_dlrm_train_jit(cfg, optimizer, dlrm.bce_loss)

    def text(fn, *args):
        return fn.lower(*args).compile().as_text()

    return {
        "decide": text(decide, esd, sparse),
        "advance": text(advance, esd, sparse, dense, labels, assign,
                        jnp.zeros((V,), bool)),
        "train_jit": text(train_jit, params, optimizer.init(params), sparse,
                          dense, labels),
    }


def _has_scope(op_names, scope):
    """Some op_name holds ``scope`` as a component of its name stack, or
    inside the ``jvp(...)``/``transpose(...)`` of one."""
    pat = re.compile(r"(^|/|\()" + re.escape(scope) + r"(\)|/)")
    return any(pat.search(name) for name in op_names)


@pytest.mark.parametrize("stage,scopes", [
    ("decide", ("esd.decide",)),
    ("advance", ("esd.advance", "esd.exchange", "esd.cache_update",
                 "universe", "phases", "capacity_cut")),
    ("train_jit", ("dlrm.train_step", "dlrm.forward", "optim.update")),
])
def test_compiled_stage_carries_its_scopes(compiled, stage, scopes):
    names = set(re.findall(r'op_name="([^"]*)"', compiled[stage]))
    assert names
    for scope in scopes:
        assert _has_scope(names, scope), (stage, scope)
    # the cut's sort runs under its own scope, below the update's
    if stage == "advance":
        assert _has_scope({n for n in names if "capacity_cut" in n
                           and n.endswith("/sort")}, "esd.cache_update")


_DEF = re.compile(r"^\s*(?:ROOT )?%([\w.\-]+) = [a-z0-9]+\[([\d,]*)\]")
_COMP = re.compile(r"^(?:ENTRY )?%([\w.\-]+) .*\{$")


def cross_products(hlo_text, d, r):
    """The op_name under which each matrix product of a low-rank cross
    (the ``dot`` or ``convolution`` whose operands and output span both
    the cross's width ``d`` and its rank ``r``) is filed: that of the
    outermost fusion that holds it, as the device trace names it."""
    comp, owner, dims, caller, products = None, {}, {}, {}, []
    for line in hlo_text.splitlines():
        m = _COMP.match(line)
        if m:
            comp = m.group(1)
            continue
        m = _DEF.match(line)
        if m:
            dims[m.group(1)] = {int(x) for x in m.group(2).split(",") if x}
        name = line.strip().removeprefix("ROOT ").partition(" = ")[0]
        owner[name.lstrip("%")] = comp
        call = re.search(r" fusion\(.*calls=%([\w.\-]+)", line)
        if call:
            caller[call.group(1)] = line
        op = re.search(r" (dot|convolution)\((%[\w.\-]+), (%[\w.\-]+)\)",
                       line)
        if op and m:
            spans = dims[m.group(1)].union(
                *(dims.get(a.lstrip("%"), set()) for a in op.groups()[1:]))
            if {d, r} <= spans:
                products.append((comp, line))
    out = []
    for comp, line in products:
        while comp in caller:
            line = caller[comp]
            comp = owner[line.strip().removeprefix("ROOT ").partition(
                " = ")[0].lstrip("%")]
        out.append(re.search(r'op_name="([^"]*)"', line).group(1))
    return out


def assert_cross_scoped(names, layers):
    """Two products a layer forward and four backward (the transposes),
    every one filed under ``dlrm.cross``."""
    assert all(_has_scope({n}, "dlrm.cross") for n in names), names
    backward = [n for n in names if "transpose(" in n]
    assert (len(names) - len(backward), len(backward)) == (
        2 * layers, 4 * layers), names


@pytest.mark.parametrize("arch,scopes", [
    ("dcn-tiny", ("dlrm.cross",)),
    ("dcnv2-tiny", ("dlrm.bag_pool", "dlrm.cross")),
])
def test_train_step_names_its_interaction(arch, scopes):
    """The bags and the cross net, forward and backward, under their own
    scopes inside the train step; the low-rank cross's every matrix
    product among them."""
    cfg = DLRM_CONFIGS[arch]
    wl = WORKLOADS[cfg.workload]
    rng = np.random.default_rng(0)
    optimizer = get_optimizer("rowwise_adagrad", 1e-2)
    params = dlrm.init_params(jax.random.key(0), cfg, wl)
    train_jit = make_dlrm_train_jit(cfg, optimizer, dlrm.bce_loss)
    # 5 rows: a batch size that no width of the tiny models shares
    text = train_jit.lower(
        params, optimizer.init(params),
        jnp.asarray(wl.sample_batch(rng, 5), jnp.int32),
        jnp.asarray(wl.dense_batch(rng, 5)),
        jnp.asarray(wl.label_batch(rng, 5))).compile().as_text()
    names = set(re.findall(r'op_name="([^"]*)"', text))
    for scope in scopes:
        assert _has_scope(names, scope), scope
        assert _has_scope({n for n in names if "transpose(" in n}, scope)
    if cfg.kind == "dcnv2":
        d = cfg.bottom_widths[-1] + cfg.embedding_dim * wl.n_fields
        assert_cross_scoped(cross_products(text, d, cfg.cross_rank),
                            cfg.cross_layers)
