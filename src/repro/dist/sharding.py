"""PartitionSpec trees for every pytree the launcher and dry-run move:
params, optimizer state, train batches, decode caches.

One rule engine covers all model families (dense / MoE / SSM / hybrid /
audio / DLRM): a leaf's spec is derived from its *name* in the pytree path
plus the arch config, then fitted to the leaf's actual rank and shape —

  * scan-stacked layer groups (``params["groups"]``, whisper ``enc``/
    ``dec``) carry extra leading axes; the named pattern describes the
    trailing (per-layer) dims and is left-padded with None, so the same
    rule serves both the stacked and the ``rest`` copies of a layer;
  * a "model"-sharded entry is kept only when the model-axis size divides
    the dim (vocab 51866 on a 16-wide axis stays replicated — the same
    rule the dry-run's logits spec applies); every spec therefore has
    ``len(spec) == leaf.ndim`` for every leaf of every arch, which is the
    invariant tests/test_dist.py property-checks.

Entry points (the dry-run/launcher/hillclimb surface):

  param_specs(tree, cfg=None, model_size=16)  params or optimizer state
  batch_specs(cfg, shape, mesh)               train/prefill input batch
  cache_specs(cfg, cache, mesh, batch)        decode cache
  data_axes(mesh)                             batch-carrying mesh axes
  zero1_specs(specs, shapes, mesh)            ZeRO-1 optimizer-state shard
  exchange_specs(mesh)                        ragged-exchange buffer views
  to_shardings(specs, mesh=None)              P tree -> NamedSharding tree

``model_size`` defaults to the production mesh's 16-wide model axis
(launch.mesh.make_production_mesh); pass 1 for single-host replication.
"""
from __future__ import annotations

from typing import Any

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from . import ctx
from .ctx import axis_size, data_axes

# model-axis width of the production mesh (launch/mesh.py) — the default
# target when the caller hands us a config but no mesh.
PRODUCTION_MODEL_SIZE = 16

__all__ = [
    "param_specs", "batch_specs", "cache_specs", "data_axes",
    "zero1_specs", "to_shardings", "exchange_specs",
    "PRODUCTION_MODEL_SIZE",
]

_M = "model"


def _is_spec(x) -> bool:
    return isinstance(x, P)


# --------------------------------------------------------------------------
# rule tables: param name -> pattern over the param's trailing (logical)
# dims.  "model" entries are dropped per-leaf when the dim doesn't divide.
# --------------------------------------------------------------------------
def _attn_axes(mode: str):
    """(kv_axis, group_axis) for the explicit GQA weight layout."""
    return (_M if mode == "kv" else None, _M if mode == "g" else None)


def _lm_rules(mode: str) -> dict[str, tuple]:
    kv_ax, g_ax = _attn_axes(mode)
    return {
        # embeddings / head: vocab over the model axis (row-sharded table)
        "embed": (_M, None),
        "lm_head": (None, _M),
        # attention, explicit (D, KV, G, hd) layout (models/layers.py)
        "wq": (None, kv_ax, g_ax, None),
        "wk": (None, kv_ax, None),
        "wv": (None, kv_ax, None),
        "attn.wo": (kv_ax, g_ax, None, None),
        "xattn.wo": (kv_ax, g_ax, None, None),
        # dense MLP (tensor parallel: ff out, ff in); the bare names also
        # catch llama4's shared expert ({"ffn": {"shared": {"wi": ...}}})
        "ffn.wi": (None, _M),
        "ffn.wg": (None, _M),
        "ffn.wo": (_M, None),
        "wi": (None, _M),
        "wg": (None, _M),
        "wo": (_M, None),
        # MoE stacked experts: expert-parallel over the model axis
        "router": (None, _M),
        "moe.wi": (_M, None, None),
        "moe.wg": (_M, None, None),
        "moe.wo": (_M, None, None),
        # mamba (d_inner = expand * d_model shards over model)
        "in_proj": (None, _M),
        "conv_w": (None, _M),
        "conv_b": (_M,),
        "x_dt": (_M, None),
        "dt_proj": (None, _M),
        "dt_bias": (_M,),
        "x_B": (_M, None),
        "x_C": (_M, None),
        "A_log": (_M, None),
        "D": (_M,),
        "out_proj": (_M, None),
        # RG-LRU (lru_width shards over model)
        "in_x": (None, _M),
        "in_gate": (None, _M),
        "gate_a": (None, _M),
        "gate_x": (None, _M),
        "Lambda": (_M,),
        "out": (_M, None),
    }


def _dlrm_rules() -> dict[str, tuple]:
    """PS-style DLRM placement: the (V, E) global embedding table (and the
    wide (V, 1) term) row-sharded over the data axis — each worker holds a
    V/n slice, exactly the per-worker cache plane the ESD engine manages —
    while the interaction/MLP stack is replicated.

    repro.ps addressing: under multi-PS training the table arrives
    PS-stacked as (n_ps, max_rows, E) — ``repro.ps.PsPartition`` maps a
    global id to ``(ps_shard, local_row)`` and the row block ``[p]`` is
    exactly the rows parameter server ``p`` owns (lookups index the
    flattened table at the PS-linearized id ``p * max_rows + local``).
    The placement those leaves get (see :func:`_dlrm_ps_spec`) shards the
    leading PS axis over the data axis — one shard group per server —
    falling back to sharding ``max_rows`` (rows *within* every PS block)
    when n_ps doesn't divide the axis, and to replication otherwise.
    """
    return {"embed": ("data", None), "wide": ("data", None)}


# PS-stacked (n_ps, max_rows, ...) table leaves: prefer one device group
# per parameter server, then rows-within-shard, then replicate.
_DLRM_PS_PATTERNS = (("data", None, None), (None, "data", None))


def _dlrm_ps_spec(shape, fit_ctx) -> P:
    for pat in _DLRM_PS_PATTERNS:
        spec = _fit(pat, shape, fit_ctx)
        if any(e is not None for e in spec):
            return spec
    return P(*([None] * len(shape)))


def _path_names(path) -> list[str]:
    """Dict/attr keys along a tree path, innermost last (list indices and
    the like are skipped)."""
    names = []
    for p in path:
        if hasattr(p, "key") and isinstance(getattr(p, "key"), str):
            names.append(p.key)
        elif hasattr(p, "name"):
            names.append(str(p.name))
    return names


def _lookup(rules: dict[str, tuple], names: list[str]):
    """Resolve a leaf's rule from its path names, innermost-first.

    ``{"w": ...}`` wrappers (init_linear) are transparent; qualified
    "parent.name" keys ("attn.wo", "ffn.wi") are tried before bare names
    so the distinct "wo" layouts (attention rank-4 vs MLP rank-2) can't
    collide.
    """
    names = [n for n in names if n != "w"]
    for i in range(len(names) - 1, -1, -1):
        name, parent = names[i], names[i - 1] if i else ""
        qualified = f"{parent}.{name}"
        if qualified in rules:
            return name, rules[qualified]
        if name in rules:
            return name, rules[name]
    return None, None


def _fit(pattern, shape, mesh_or_size) -> P:
    """Fit a trailing-dims pattern to a concrete leaf shape.

    Left-pads with None for scan-stack axes and drops any sharded entry
    whose axis size does not divide the dim.
    """
    if pattern is None or len(shape) < len(pattern):
        return P(*([None] * len(shape)))
    entries = [None] * (len(shape) - len(pattern)) + list(pattern)
    out = []
    for dim, e in zip(shape, entries):
        if e is None:
            out.append(None)
            continue
        # mesh-like (Mesh or AbstractMesh) vs plain model-axis width
        size = (axis_size(mesh_or_size, e)
                if hasattr(mesh_or_size, "axis_names") else mesh_or_size)
        out.append(e if size > 0 and dim % size == 0 else None)
    return P(*out)


# --------------------------------------------------------------------------
# params / optimizer state
# --------------------------------------------------------------------------
def param_specs(tree: Any, cfg=None, model_size: int | None = None,
                mesh: Mesh | None = None):
    """PartitionSpec tree (same structure) for a params-shaped pytree.

    ``tree`` may hold concrete arrays or ShapeDtypeStructs (the dry-run's
    ``launch.steps.param_shapes`` output) — only ``.shape`` is read.
    Optimizer state nests param paths under mu/nu/…, which resolves through
    the same innermost-name rules; unrecognized leaves (adam's step
    counter, rowwise-adagrad row accumulators) replicate at their own rank.

    ``cfg=None`` selects the DLRM placement (PS-row-sharded table); LM
    configs pick head axes via ``ctx.attn_mode(cfg, model_size)``.  Pass
    ``mesh`` to fit divisibility against the actual axis sizes (required
    for the DLRM "data"-sharded table — a vocab that doesn't divide the
    worker count must fall back to replicated, not crash device_put).

    Multi-PS DLRM: rank-3 embed/wide leaves are treated as PS-stacked
    (n_ps, max_rows, ...) tables (see :func:`_dlrm_rules` on the
    repro.ps (shard, local_row) convention) and get the per-PS placement.
    """
    is_dlrm = cfg is None or getattr(cfg, "family", None) == "dlrm"
    if is_dlrm:
        rules: dict[str, tuple] = _dlrm_rules()
        # no mesh -> assume divisible (specs are validated by to_shardings
        # callers against a real mesh anyway)
        fit_ctx: Any = mesh if mesh is not None else 1
    else:
        if model_size is None:
            model_size = (mesh.shape[_M] if mesh is not None
                          else PRODUCTION_MODEL_SIZE)
        rules = _lm_rules(ctx.attn_mode(cfg, model_size))
        fit_ctx = mesh if mesh is not None else model_size

    def one(path, leaf):
        names = _path_names(path)
        # PS-stacked DLRM tables: (n_ps, max_rows, ...) under embed/wide
        if (is_dlrm and names and names[-1] in ("embed", "wide")
                and len(leaf.shape) == 3):
            return _dlrm_ps_spec(leaf.shape, fit_ctx)
        # MoE expert stacks: raw rank-3 arrays directly under "ffn"
        if (names and names[-1] in ("wi", "wg", "wo")
                and len(names) >= 2 and names[-2] == "ffn"):
            key = f"moe.{names[-1]}"
            if key in rules and len(leaf.shape) >= len(rules[key]):
                return _fit(rules[key], leaf.shape, fit_ctx)
        _, pattern = _lookup(rules, names)
        # _fit replicates leaves whose rank is below the pattern's
        # (e.g. rowwise-adagrad's (V,) accumulator for a (V, E) table)
        return _fit(pattern, leaf.shape, fit_ctx)

    return jax.tree_util.tree_map_with_path(one, tree)


# --------------------------------------------------------------------------
# batches
# --------------------------------------------------------------------------
def batch_specs(cfg, shape, mesh: Mesh):
    """Input-batch specs: leading (global-batch) dim over the data axes,
    everything else replicated.  Matches launch.steps.batch_shapes."""
    from ..launch.steps import batch_shapes

    dp = data_axes(mesh)
    dsize = axis_size(mesh, dp)

    def one(leaf):
        b_ax = dp if leaf.shape and leaf.shape[0] % dsize == 0 else None
        return P(b_ax, *([None] * (len(leaf.shape) - 1)))

    return jax.tree.map(one, batch_shapes(cfg, shape))


# --------------------------------------------------------------------------
# decode caches
# --------------------------------------------------------------------------
def _cache_rules(cfg, mode: str) -> dict[str, tuple]:
    kv_ax, _ = _attn_axes(mode)
    B = "__batch__"   # placeholder resolved to the data axes per leaf
    return {
        # KV ring: (B, C, KV, hd); whisper cross K/V: (B, enc, KV, hd)
        "k": (B, None, kv_ax, None),
        "v": (B, None, kv_ax, None),
        "cross_k": (B, None, kv_ax, None),
        "cross_v": (B, None, kv_ax, None),
        "pos": None,                      # (C,) slot positions: replicated
        "conv": (B, None, _M),            # (B, K-1, channels)
        "ssm": (B, _M, None),             # (B, d_inner, N)
        "h": (B, _M),                     # (B, lru_width)
    }


def cache_specs(cfg, cache: Any, mesh: Mesh, global_batch: int):
    """Decode-cache specs: batch dim over the data axes (when it divides),
    KV heads over the model axis per the arch's attn mode, SSM/RG-LRU
    channel states over the model axis.  Stack axes (layer groups, whisper
    L) are left-padded exactly like param_specs.  ``global_batch`` is part
    of the dry-run call contract; divisibility is decided per leaf from
    the actual shapes, which subsumes it."""
    mode = ctx.attn_mode(cfg, mesh.shape[_M])
    rules = _cache_rules(cfg, mode)
    dp = data_axes(mesh)

    def one(path, leaf):
        _, pattern = _lookup(rules, _path_names(path))
        if pattern is None:
            return P(*([None] * len(leaf.shape)))
        pattern = tuple(dp if e == "__batch__" else e for e in pattern)
        return _fit(pattern, leaf.shape, mesh)

    return jax.tree_util.tree_map_with_path(one, cache)


# --------------------------------------------------------------------------
# ragged-exchange buffers
# --------------------------------------------------------------------------
def exchange_specs(mesh: Mesh | None = None):
    """Specs for the ragged exchange's bucketed buffers as seen OUTSIDE
    shard_map (repro.exchange.ragged runs inside; these place the global
    views a driver or test stacks up):

      * ``send`` / ``recv`` — (n_src, n_dst, budget, F) blocks with the
        source axis over the data axes (each shard owns the blocks it
        puts on / takes off the wire);
      * ``counts`` — the (n_src, n_dst) valid-row matrix, source-sharded
        to match (it is all_gather'd on device, so the global view is
        replicated after exchange — this spec is the pre-gather layout);
      * ``out`` — the compacted (k_out, F) batch, row-sharded like any
        per-sample array.
    """
    dp = data_axes(mesh) if mesh is not None else "data"
    return {
        "send": P(dp, None, None, None),
        "recv": P(dp, None, None, None),
        "counts": P(dp, None),
        "out": P(dp, None),
    }


# --------------------------------------------------------------------------
# ZeRO-1
# --------------------------------------------------------------------------
def zero1_specs(specs: Any, shapes: Any, mesh: Mesh):
    """ZeRO-1: additionally shard each optimizer-state leaf over the data
    axes — the state is only read/written around the (already summed)
    gradient, so partitioning it removes the dominant per-device copy.

    For every leaf the first still-replicated dim the data-axis size
    divides is switched to the data axes; leaves with no such dim (small
    vectors, scalars) stay put.  Model-axis entries are preserved, so a
    leaf ends up sharded over both axes when shapes allow.
    """
    dp = data_axes(mesh)
    dsize = axis_size(mesh, dp)

    def one(spec, leaf):
        entries = list(spec)
        for i, dim in enumerate(leaf.shape):
            if entries[i] is None and dim >= dsize and dim % dsize == 0:
                entries[i] = dp
                return P(*entries)
        return spec

    return jax.tree.map(one, specs, shapes, is_leaf=_is_spec)


# --------------------------------------------------------------------------
# materialization
# --------------------------------------------------------------------------
def to_shardings(specs: Any, mesh: Mesh | None = None):
    """Map a PartitionSpec tree to a NamedSharding tree on ``mesh``.

    With ``mesh=None`` a (n_devices, 1) ("data", "model") host mesh is
    built — the single-process default the launcher trains on.  Entries
    naming axes the mesh doesn't have (e.g. "pod" specs on a single-pod
    mesh) are dropped rather than erroring, so production specs stay
    usable on host meshes.
    """
    if mesh is None:
        from ..launch.mesh import make_host_mesh
        mesh = make_host_mesh()

    def one(spec: P) -> NamedSharding:
        entries = []
        for e in spec:
            names = e if isinstance(e, tuple) else (e,)
            if e is not None and all(n in mesh.axis_names for n in names):
                entries.append(e)
            else:
                entries.append(None)
        return NamedSharding(mesh, P(*entries))

    return jax.tree.map(one, specs, is_leaf=_is_spec)
