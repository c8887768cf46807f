"""Model kinds are files found by name (``bench/models/<kind>.py``).

WDL, DeepFM and DCN give, through their kind modules, bitwise the
numbers of the reference and work counts as they stood before the
kinds moved into files (``parent_reference``, ``parent_work``).  A new
kind is a module, a configuration and a cell added as new files, and
the reference, the sampler and the work counts find it in the copy it
was added to.  The program's configuration and workload take every
configuration key and sampler attribute that names one of their
fields."""
import dataclasses
import json
import math
from pathlib import Path

import jax.numpy as jnp
import pytest

from bench import program, reference, run, traffic, work
from bench.tests import parent_reference, parent_work, tiny
from bench.tests.test_bench_harness import digest
from repro.configs import DLRM_CONFIGS
from repro.configs.dlrm_configs import DLRMConfig
from repro.data.synthetic import WORKLOADS

KINDS = {"wdl": tiny.TINY, "dfm": dict(tiny.TINY, kind="dfm"),
         "dcn": dict(tiny.TINY, kind="dcn", cross_layers=2)}
VARIANTS = {"reference": {}, "bf16": {"dtype": jnp.bfloat16},
            "fp8": {"operands": jnp.float8_e4m3fn}, "half_batch": {"keep": 0.5}}
SEED = 2 ** 31 + 9


def _batches(cfg):
    sampler = traffic.CTRSampler(cfg["tables"])
    return [b for _, b in zip(range(3), sampler.batches(SEED + 1, 16))]


@pytest.mark.parametrize("variant", VARIANTS)
@pytest.mark.parametrize("kind", KINDS)
def test_train_steps_match_the_parent_bitwise(kind, variant):
    cfg, kw = KINDS[kind], VARIANTS[variant]
    batches = _batches(cfg)
    got = reference.train_steps(reference.Model(cfg), SEED, batches, 0.01,
                                **kw)
    want = parent_reference.train_steps(parent_reference.Model(cfg), SEED,
                                        batches, 0.01, **kw)
    assert got == want
    assert all(math.isfinite(x) for x in got["loss"])


@pytest.mark.parametrize("kind", KINDS)
def test_work_counts_match_the_parent(kind):
    cfg = KINDS[kind]
    for rows in (1, 128):
        assert work.forward_flops(cfg, rows) == parent_work.forward_flops(
            cfg, rows)
        for distinct in (7, 2600.5):
            assert work.train_step(cfg, rows, distinct) == \
                parent_work.train_step(cfg, rows, distinct)
    assert work.dense_params(cfg) == parent_work.dense_params(cfg)


def test_unknown_kind_is_refused():
    with pytest.raises(ValueError, match="unknown model kind"):
        reference.Model(dict(tiny.TINY, kind="nope"))


TOY = '''"""A toy kind: logistic regression over the mean of the embedded
ids, with a sampler of its own that draws no history bag."""
import jax
import jax.numpy as jnp

from bench import reference as R
from bench.traffic import CTRSampler


class Sampler(CTRSampler):
    def __init__(self, spec):
        super().__init__(dict(spec, hist_max=0))


def tables(cfg):
    return {"embed": int(cfg["embedding_dim"])}


def init(model, dtype, key):
    E = model["embedding_dim"]
    return {"embed": R.normal(key, (model.V, E), 0.01, dtype),
            "w": R.normal(jax.random.fold_in(key, 1), (E,), 1.0, dtype)}


def forward(model, params, sparse, dense):
    valid = sparse >= 0
    rows = params["embed"][jnp.where(valid, sparse, 0)]
    rows = rows * valid[..., None].astype(rows.dtype)
    n = jnp.maximum(valid.sum(axis=1, keepdims=True), 1)
    return (rows.sum(axis=1) / n.astype(rows.dtype)) @ params["w"]


def dense_params(cfg):
    return int(cfg["embedding_dim"])


def forward_flops(cfg, rows):
    return float(rows) * 2 * int(cfg["embedding_dim"])
'''
TOY_CFG = {"name": "toy", "source": "test", "kind": "toy",
           "embedding_dim": 8, "tables": tiny.TINY["tables"]}


def test_new_kind_is_files_found_by_name(tmp_path):
    root = tiny.copy_bench(tmp_path)
    before = digest(root)
    (root / "bench" / "models" / "toy.py").write_text(TOY)
    tiny.add_cell(root, "toy.train", TOY_CFG, "tiny.train", tiny.TRAIN,
                  tiny.limits("wdl-s1.esd.1c"))
    after = digest(root)
    assert {k: after[k] for k in before} == before    # nothing edited

    cell = run.Cell(root, "toy.train")
    model = reference.Model(cell.config, cell.root)
    assert Path(model.module.__file__) == root / "bench" / "models" / "toy.py"
    assert dict(model.table_leaves) == {"embed": 8}
    sampler = traffic.sampler(cell.config, cell.root)
    assert type(sampler).__name__ == "Sampler"
    assert sampler.width == model.F                    # no history bag
    batches = [b for _, b in zip(range(3), sampler.batches(SEED, 16))]
    ref = reference.train_steps(model, SEED, batches, 0.01)
    assert all(math.isfinite(x) and x > 0 for x in ref["loss"])
    assert set(ref["grad_norm"]) == {"['embed']", "['w']"}
    assert ref["drift"] == {"['embed']": 0.0}
    assert work.train_step(cell.config, 16, 20.0, root=cell.root) == {
        "flops": 3 * 16 * 2 * 8, "bytes": 2 * 4 * (20.0 * (8 + 1) + 8)}
    # the checkout's own benchmark has no such kind
    with pytest.raises(ValueError, match="unknown model kind"):
        reference.Model(cell.config)


def _config(name):
    return json.loads((tiny.ROOT / "bench" / "configs" / f"{name}.json")
                      .read_text())


@pytest.mark.parametrize("name", ["wdl-s1", "dcn-s3"])
def test_program_gets_todays_fields(name):
    cfg = _config(name)
    sampler = traffic.sampler(cfg)
    with program.registered(name, cfg, sampler) as key:
        got, wl = DLRM_CONFIGS[key], WORKLOADS[key]
    # what the harness handed the program when it named each field
    assert got == DLRMConfig(
        key, cfg["kind"], key, embedding_dim=int(cfg["embedding_dim"]),
        n_dense=sampler.n_dense, mlp_dims=tuple(cfg["mlp_dims"]),
        cross_layers=int(cfg.get("cross_layers", 0)))
    assert wl == program.BenchWorkload(
        name=key, model=cfg["kind"], table_sizes=sampler.sizes,
        zipf_a=sampler.zipf_a, n_dense=sampler.n_dense,
        n_groups=sampler.n_groups, group_frac=sampler.group_frac,
        hist_max=sampler.hist_max, hist_mean=sampler.hist_mean)
    assert key not in DLRM_CONFIGS and key not in WORKLOADS


def test_a_new_program_field_arrives_unedited(monkeypatch):
    @dataclasses.dataclass(frozen=True)
    class Wider(DLRMConfig):
        bottom_dims: tuple = ()

    monkeypatch.setattr(program, "DLRMConfig", Wider)
    cfg = dict(_config("dcn-s3"), bottom_dims=[512, 256, 128])
    with program.registered("wider", cfg, traffic.sampler(cfg)) as key:
        assert DLRM_CONFIGS[key].bottom_dims == (512, 256, 128)
        assert DLRM_CONFIGS[key].cross_layers == 3
