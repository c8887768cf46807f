"""repro.dist sharding-spec layer: round-trip validity of the spec trees
on real and mocked meshes, plus the rank invariant as a property test.

The invariant the dry-run and launcher rely on: for every leaf of every
pytree we shard (params, optimizer state, batches, decode caches),
``len(spec) == leaf.ndim`` and every sharded dim is divisible by its mesh
axes — so ``NamedSharding.shard_shape`` never fails and GSPMD never sees
a rank-mismatched constraint.
"""
import jax
import numpy as np
import pytest
from jax.sharding import AbstractMesh, AxisType, NamedSharding, PartitionSpec as P

from hypothesis import given, settings, strategies as st

from repro.configs import CONFIGS, INPUT_SHAPES, SMOKE_CONFIGS
from repro.dist import ctx
from repro.dist.sharding import (
    batch_specs,
    cache_specs,
    data_axes,
    param_specs,
    to_shardings,
    zero1_specs,
)
from repro.launch.steps import (
    batch_shapes,
    cache_shapes,
    opt_state_shapes,
    param_shapes,
)
from repro.optim import get_optimizer

ARCHS = ("smollm-360m", "llama4-scout-17b-a16e", "falcon-mamba-7b",
         "whisper-large-v3", "recurrentgemma-2b")

_is_spec = lambda x: isinstance(x, P)
_SHAPES = {}    # param_shapes is an eval_shape trace; compute once per arch


def _shapes(arch):
    if arch not in _SHAPES:
        _SHAPES[arch] = param_shapes(SMOKE_CONFIGS[arch])
    return _SHAPES[arch]


def _pairs(shapes, specs):
    a = jax.tree.leaves(shapes)
    b = jax.tree.leaves(specs, is_leaf=_is_spec)
    assert len(a) == len(b)
    return zip(a, b)


def _mock_mesh(data=16, model=16):
    """A 256-device production-shaped mesh with no physical devices —
    lets a single-CPU test validate multi-device placements."""
    return AbstractMesh((data, model), ("data", "model"),
                        axis_types=(AxisType.Auto, AxisType.Auto))


class TestRoundTrip:
    @pytest.mark.parametrize("arch", ARCHS)
    def test_one_device_mesh(self, arch):
        """to_shardings(param_specs(...)) must materialize on the default
        single-host mesh and shard nothing (every axis is 1 wide)."""
        shapes = _shapes(arch)
        specs = param_specs(shapes, SMOKE_CONFIGS[arch], model_size=1)
        shardings = to_shardings(specs)            # default host mesh
        for leaf, sh in _pairs(shapes, shardings):
            assert isinstance(sh, NamedSharding)
            assert sh.shard_shape(leaf.shape) == leaf.shape, (arch, leaf.shape)

    @pytest.mark.parametrize("arch", ARCHS)
    def test_mocked_multidevice_mesh(self, arch):
        """Same specs on a mocked 16x16 mesh: every sharded dim divides its
        axes, so shard_shape succeeds and shrinks exactly by the shards."""
        mesh = _mock_mesh()
        shapes = _shapes(arch)
        specs = param_specs(shapes, SMOKE_CONFIGS[arch],
                            model_size=mesh.shape["model"])
        shardings = to_shardings(specs, mesh)
        n_sharded = 0
        for leaf, sh in _pairs(shapes, shardings):
            got = sh.shard_shape(leaf.shape)       # raises on bad specs
            shards = np.prod([ctx.axis_size(mesh, e) for e in sh.spec] or [1])
            assert np.prod(leaf.shape) == np.prod(got) * shards
            n_sharded += any(e is not None for e in sh.spec)
        # the layer must actually partition something on every arch
        assert n_sharded > 0, arch

    def test_dlrm_table_respects_data_axis_divisibility(self):
        """DLRM (cfg=None) placement against a real mesh: the PS-row shard
        survives only when the vocab divides the worker count, otherwise
        the table replicates instead of blowing up device_put."""
        tree = {
            "embed": jax.ShapeDtypeStruct((408_500, 16), np.float32),  # %8!=0
            "wide": jax.ShapeDtypeStruct((400_000, 1), np.float32),   # %8==0
            "bottom": [{"w": jax.ShapeDtypeStruct((13, 64), np.float32)}],
        }
        specs = param_specs(tree, mesh=_mock_mesh(data=8, model=1))
        assert specs["embed"] == P(None, None)
        assert specs["wide"] == P("data", None)
        assert specs["bottom"][0]["w"] == P(None, None)
        # without a mesh the spec is optimistic; to_shardings still maps it
        assert param_specs(tree)["embed"] == P("data", None)

    def test_pod_specs_degrade_to_host_mesh(self):
        """Production specs naming the pod axis stay usable on single-pod
        meshes: unknown axes are dropped, not an error."""
        specs = {"x": P(("pod", "data"), None), "y": P("model")}
        sh = to_shardings(specs, _mock_mesh())      # no "pod" axis
        assert sh["x"].spec == P(None, None)
        assert sh["y"].spec == P("model")


class TestDerivedSpecs:
    def test_batch_specs_match_batch_shapes(self):
        mesh = _mock_mesh()
        for arch in ARCHS:
            cfg = SMOKE_CONFIGS[arch]
            shape = INPUT_SHAPES["train_4k"]
            shapes = batch_shapes(cfg, shape)
            specs = batch_specs(cfg, shape, mesh)
            for leaf, spec in _pairs(shapes, specs):
                assert len(spec) == len(leaf.shape)
                assert spec[0] == data_axes(mesh)   # batch dim sharded

    def test_cache_specs_match_cache_shapes(self):
        mesh = _mock_mesh()
        shape = INPUT_SHAPES["decode_32k"]
        for arch in ARCHS:
            cfg = SMOKE_CONFIGS[arch]
            shapes = cache_shapes(cfg, shape)
            specs = cache_specs(cfg, shapes, mesh, shape.global_batch)
            for leaf, spec in _pairs(shapes, specs):
                assert len(spec) == len(leaf.shape), (arch, leaf.shape, spec)

    def test_zero1_adds_data_axis_to_opt_state(self):
        mesh = _mock_mesh()
        cfg = SMOKE_CONFIGS["smollm-360m"]
        oshapes = opt_state_shapes(cfg, get_optimizer("adam", 1e-3))
        ospecs = param_specs(oshapes, cfg, model_size=mesh.shape["model"])
        z = zero1_specs(ospecs, oshapes, mesh)
        gained = 0
        for leaf, (spec, zspec) in zip(
                jax.tree.leaves(oshapes),
                zip(jax.tree.leaves(ospecs, is_leaf=_is_spec),
                    jax.tree.leaves(z, is_leaf=_is_spec))):
            assert len(zspec) == len(leaf.shape)
            if zspec != spec:
                gained += 1
                assert data_axes(mesh) in tuple(zspec)
                # still materializable
                NamedSharding(mesh, zspec).shard_shape(leaf.shape)
        # the big 2D moment leaves must actually get the data axis
        assert gained > 0


class TestRankInvariantProperty:
    @settings(max_examples=15, deadline=None)
    @given(st.integers(0, len(ARCHS) - 1), st.integers(0, 4))
    def test_spec_rank_matches_leaf_rank(self, arch_idx, log_model):
        """For every SMOKE arch and any power-of-two model-axis width,
        every param spec has exactly the rank of its leaf."""
        arch = ARCHS[arch_idx]
        model_size = 2 ** log_model
        shapes = _shapes(arch)
        specs = param_specs(shapes, SMOKE_CONFIGS[arch],
                            model_size=model_size)
        for leaf, spec in _pairs(shapes, specs):
            assert len(spec) == len(leaf.shape), \
                (arch, model_size, leaf.shape, spec)
