"""The harness is driven by data: a cell, configuration, traffic mix,
limit or per-layer metric is a file found by name, and adding one edits
no file already there.  Without a TPU the command fails and prints no
result."""
import hashlib
import json
import os
import subprocess
import sys

import pytest

from bench import run
from bench.tests import tiny


def digest(root):
    return {p.relative_to(root): hashlib.sha256(p.read_bytes()).hexdigest()
            for p in sorted((root / "bench").rglob("*")) if p.is_file()}


def test_every_cell_resolves():
    bench = json.loads((tiny.ROOT / "BENCHMARK.json").read_text())
    for w in bench["workloads"]:
        cell = run.Cell(tiny.ROOT, w["name"])
        assert cell.per_layer and cell.end_to_end
        assert cell.traffic["kind"] == "train"
        assert set(cell.limits) >= {"compiles_in_window"}


def test_new_cell_and_metric_are_files_found_by_name(tmp_path):
    root = tiny.copy_bench(tmp_path)
    before = digest(root)
    tiny.add_cell(root, "tiny.train", tiny.TINY, "tiny.train", tiny.TRAIN,
                  {"loss_gap": 1.0})
    metric = root / "bench" / "metrics" / "steps_seen.train.py"
    metric.write_text("def read(ctx):\n    return ctx['steps']\n")
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["per_layer"].append({
        "name": "steps_seen.train", "unit": "steps", "better": "higher",
        "source": "device_trace", "layer": "driver and pipeline",
        "moves": "train_samples_per_s", "workloads": ["tiny.train"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    after = digest(root)
    assert {k: after[k] for k in before} == before    # nothing edited
    cell = run.Cell(root, "tiny.train")
    assert cell.config == tiny.TINY and cell.traffic == tiny.TRAIN
    assert "steps_seen.train" in cell.metric_files
    assert run.read_metric(cell.metric_files["steps_seen.train"],
                           {"steps": 7}) == 7
    assert [m["name"] for m in cell.end_to_end] == [
        "train_samples_per_s", "setup_s"]


def test_missing_file_is_refused(tmp_path):
    root = tiny.copy_bench(tmp_path)
    (root / "bench" / "traffic" / "esd.1c.json").unlink()
    with pytest.raises(SystemExit):
        run.Cell(root, "wdl-s1.esd.1c")


def _cmd(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("ALLOW_MULTIPLE_LIBTPU_LOAD", None)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "wdl-s1.esd.1c",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_no_tpu_no_result():
    out = _cmd(tiny.ROOT)
    assert out.returncode != 0
    assert "needs a TPU" in out.stderr
    assert not out.stdout.strip()


def test_benchmark_files_alone_no_result(tmp_path):
    out = _cmd(tiny.copy_bench(tmp_path))
    assert out.returncode != 0
    assert not out.stdout.strip()
