"""Benchmark-driver smoke: the benchmarks must keep importing and doing a
tiny-config run — they are the only callers of some repro.dist wiring
(zero1_specs, MOE block specs, OPT_SPEC_TRANSFORM), so a silent import
break there would only surface when someone next hillclimbs."""
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]


def _run_py(code, timeout=300):
    env = {"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
           "HOME": os.environ.get("HOME", str(ROOT)),
           "JAX_PLATFORMS": os.environ.get("JAX_PLATFORMS", "cpu")}
    return subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)], capture_output=True,
        text=True, timeout=timeout, cwd=ROOT, env=env,
    )


def test_hillclimb_imports_and_variant_hooks():
    """benchmarks/hillclimb.py must import cleanly (it pulls dryrun, which
    owns XLA_FLAGS mangling — hence the subprocess) and its variant hooks
    must reach into repro.dist and back out."""
    res = _run_py("""
        import benchmarks.hillclimb as hc
        from repro.dist import ctx
        from repro.dist.sharding import zero1_specs
        from repro.launch import dryrun, steps

        assert hc.zero1_specs is zero1_specs
        assert set(hc.PAIRS), "no hillclimb pairs registered"

        hc.apply_variant("combo", "llama4-scout-17b-a16e")
        assert ctx.MOE_BLOCKS == 16 and ctx.MOE_BLOCK_SPECS is not None
        assert dryrun.OPT_SPEC_TRANSFORM is zero1_specs
        kw = hc.apply_variant("no_remat", "granite-34b")
        assert kw == {"remat": False}
        hc.clear_variant()
        assert ctx.MOE_BLOCKS == 1 and ctx.MOE_BLOCK_SPECS is None
        assert dryrun.OPT_SPEC_TRANSFORM is None and steps.GRAD_DTYPE is None
        print("HILLCLIMB_OK")
    """)
    assert res.returncode == 0, res.stderr[-2000:]
    assert "HILLCLIMB_OK" in res.stdout


def test_dispatch_bench_quick_run(tmp_path):
    """dispatch_bench --quick end-to-end on the smallest vocab: report
    structure intact and the sparse jit path actually measured."""
    out = tmp_path / "bench.json"
    res = _run_py(f"""
        import json
        from pathlib import Path
        from benchmarks.dispatch_bench import run
        rep = run(quick=True, out=Path({str(out)!r}))
        r = rep["results"][0]
        assert r["V"] == 20_000
        for path in ("jit", "numpy"):
            assert r[path]["sparse_ms"] > 0 and r[path]["dense_ms"] > 0
        assert json.loads(Path({str(out)!r}).read_text())["results"]
        print("DISPATCH_BENCH_OK")
    """)
    assert res.returncode == 0, res.stderr[-2000:]
    assert "DISPATCH_BENCH_OK" in res.stdout


def test_dispatch_bench_exchange_smoke(tmp_path):
    """run_exchange quick point: padded vs ragged byte accounting plus the
    two acceptance properties — pad-byte reduction under Zipf skew and a
    strictly lower Alg.-1 cost with cap_slack."""
    out = tmp_path / "exchange.json"
    res = _run_py(f"""
        from pathlib import Path
        from benchmarks.dispatch_bench import run_exchange
        rep = run_exchange(quick=True, out=Path({str(out)!r}))
        (r,) = rep["results"]
        assert r["zipf_a"] == 1.2 and r["n"] == 8
        assert r["pad_reduction"] >= 0.30, r["pad_reduction"]
        assert r["alg1_drop"] > 0.0, r["alg1_drop"]
        assert r["ragged"]["wire_bytes"] <= r["padded"]["wire_bytes"]
        assert r["ragged"]["payload_bytes"] == r["padded"]["payload_bytes"]
        assert r["pack_ms"] > 0
        print("EXCHANGE_BENCH_OK")
    """)
    assert res.returncode == 0, res.stderr[-2000:]
    assert "EXCHANGE_BENCH_OK" in res.stdout


def test_dispatch_bench_multips_smoke(tmp_path):
    """run_multips at toy vocab: the ps sweep runs end-to-end, reports a
    row per (V, n_ps) point, and carries the sub-linearity ratios."""
    out = tmp_path / "multips.json"
    res = _run_py(f"""
        from pathlib import Path
        from benchmarks.dispatch_bench import run_multips
        rep = run_multips(vocabs=[20_000, 60_000], ps_list=[1, 2],
                          reps=1, out=Path({str(out)!r}))
        assert len(rep["results"]) == 4
        assert all(r["sparse_ms"] > 0 for r in rep["results"])
        assert set(rep["sublinear"]) == {{"1", "2"}}
        print("MULTIPS_BENCH_OK")
    """)
    assert res.returncode == 0, res.stderr[-2000:]
    assert "MULTIPS_BENCH_OK" in res.stdout
