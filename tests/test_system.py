"""End-to-end system tests: the train driver, examples surface, dry-run
machinery units (collective parsing, probe extrapolation, skip policy)."""
import json
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]


def _run(argv, timeout=900):
    # Hermetic env, except the jax platform and compile-cache selection:
    # tier-1 is a CPU suite.  XLA_FLAGS stays behind: importing
    # repro.launch.dryrun in this worker rewrites it to 512 host devices.
    env = {"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
           "HOME": os.environ.get("HOME", str(ROOT)),
           "JAX_PLATFORMS": os.environ.get("JAX_PLATFORMS", "cpu")}
    for var in ("JAX_COMPILATION_CACHE_DIR",
                "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"):
        if var in os.environ:
            env[var] = os.environ[var]
    return subprocess.run(
        [sys.executable, "-m"] + argv, capture_output=True, text=True,
        timeout=timeout, cwd=ROOT, env=env,
    )


class TestTrainDriver:
    def test_dlrm_esd_loss_and_cost_logged(self):
        res = _run(["repro.launch.train", "--arch", "wdl-tiny", "--steps",
                    "6", "--batch-per-worker", "8", "--esd-alpha", "1.0"])
        assert res.returncode == 0, res.stderr[-2000:]
        # step records go to stderr (obs.log_step); scan both streams
        recs = [json.loads(l)
                for l in (res.stdout + res.stderr).splitlines()
                if l.startswith("{")]
        assert recs and np.isfinite(recs[-1]["loss"])
        assert "miss_pull" in recs[-1] and recs[-1]["cost"] >= 0

    def test_lm_smoke_training(self):
        res = _run(["repro.launch.train", "--arch", "smollm-360m", "--smoke",
                    "--steps", "3", "--batch-per-worker", "2",
                    "--seq-len", "16"])
        assert res.returncode == 0, res.stderr[-2000:]
        recs = [json.loads(l)
                for l in (res.stdout + res.stderr).splitlines()
                if l.startswith("{")]
        assert np.isfinite(recs[-1]["loss"])


class TestDryrunUnits:
    def test_parse_collectives(self):
        from repro.launch.dryrun import parse_collectives
        hlo = "\n".join([
            "%ag = f32[16,4096,320]{1,0,2} all-gather(%x), dims={2}",
            "%ar = bf16[256,1024]{1,0} all-reduce(%y), to_apply=%add",
            "%f = f32[8,8]{1,0} fusion(%all-reduce.3), calls=%c",  # not an op
            "%a2a.1 = (f32[4,4]{1,0}, f32[4,4]{1,0}) all-to-all(%p, %q)",
            "%ard = f32[2]{0} all-reduce-done(%ar2)",               # skip
            "%ars = f32[128]{0} all-reduce-start(%z)",
        ])
        got = parse_collectives(hlo)
        assert got["all-gather"]["count"] == 1
        assert got["all-gather"]["bytes"] == 16 * 4096 * 320 * 4
        assert got["all-reduce"]["count"] == 2          # ar + ar-start
        assert got["all-reduce"]["bytes"] == (256 * 1024 * 2 + 128 * 4) * 2
        assert got["all-to-all"]["count"] == 1
        assert got["all-to-all"]["bytes"] == 2 * 4 * 4 * 4

    def test_extrapolate_linear(self):
        from repro.launch.dryrun import _extrapolate
        mk = lambda f, b: {
            "cost_analysis": {"flops": f, "bytes accessed": b},
            "collectives": {op: {"count": 1, "bytes": f / 10}
                            for op in ("all-reduce", "all-gather",
                                       "reduce-scatter", "all-to-all",
                                       "collective-permute")},
        }
        ext = _extrapolate(mk(100.0, 10.0), mk(160.0, 16.0), 5.0)
        assert ext["cost_analysis"]["flops"] == pytest.approx(100 + 60 * 4)
        assert ext["collectives"]["all-reduce"]["bytes"] == pytest.approx(
            10 + 6 * 4)

    def test_skip_policy(self):
        from repro.launch.dryrun import should_skip
        assert should_skip("yi-9b", "long_500k") is not None
        assert should_skip("falcon-mamba-7b", "long_500k") is None
        assert should_skip("recurrentgemma-2b", "long_500k") is None
        assert should_skip("llama4-scout-17b-a16e", "long_500k") is None
        assert should_skip("whisper-large-v3", "long_500k") is not None
        assert should_skip("yi-9b", "train_4k") is None

    def test_group_multiplier(self):
        from repro.configs import CONFIGS
        from repro.launch.dryrun import _group_multiplier
        assert _group_multiplier(CONFIGS["smollm-360m"]) == 32
        # recurrentgemma: 26 layers, pattern of 3 -> 8 groups + 2/3
        assert _group_multiplier(CONFIGS["recurrentgemma-2b"]) == pytest.approx(8 + 2 / 3)


class TestShardingRules:
    def test_param_specs_cover_all_leaves(self):
        import jax
        from repro.configs import SMOKE_CONFIGS
        from repro.dist.sharding import param_specs
        from repro.launch.steps import param_shapes
        for arch in ("smollm-360m", "llama4-scout-17b-a16e",
                     "falcon-mamba-7b", "whisper-large-v3",
                     "recurrentgemma-2b"):
            cfg = SMOKE_CONFIGS[arch]
            shapes = param_shapes(cfg)
            specs = param_specs(shapes, cfg)
            for leaf, spec in zip(jax.tree.leaves(shapes),
                                  jax.tree.leaves(
                                      specs,
                                      is_leaf=lambda x: hasattr(x, "index"))):
                assert len(spec) == len(leaf.shape), (arch, leaf.shape, spec)

    def test_attn_mode_selection(self):
        from repro.configs import CONFIGS
        from repro.dist.ctx import attn_mode
        assert attn_mode(CONFIGS["granite-34b"], 16) == "g"     # MQA G=48
        assert attn_mode(CONFIGS["smollm-360m"], 16) == "seq"   # 5/3 heads
        assert attn_mode(CONFIGS["yi-9b"], 16) == "seq"         # kv4 g8
        assert attn_mode(CONFIGS["yi-9b"], 4) == "kv"           # kv4 % 4
        assert attn_mode(CONFIGS["falcon-mamba-7b"], 16) == "none"
