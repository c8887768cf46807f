"""A copy of the benchmark with tiny cells added as data files, for
runs of the harness on the CPU.  Nothing in the copy's existing files
is edited: the cells, configurations, traffic mixes and limits are new
files, found by name."""
from __future__ import annotations

import json
import shutil
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]

TINY = {"name": "tiny", "source": "test", "kind": "wdl", "embedding_dim": 16,
        "mlp_dims": [64, 32],
        "tables": {"sizes": [2000, 2000, 100, 100, 100, 100],
                   "zipf_a": [1.1, 1.1, 1.05, 1.05, 1.05, 1.05],
                   "n_dense": 13, "n_groups": 32, "group_frac": 0.7,
                   "hist_max": 48, "hist_mean": 12.0}}
TRAIN = {"kind": "train", "batch_per_worker": 8, "lr": 0.01,
         "esd_alpha": 1.0, "exchange": "ragged", "pipeline_depth": 2,
         "lookahead": 4, "prefetch": 64, "prefetch_slots": 512,
         "capacity_ratio": 0.2, "warmup_steps": 5, "trace_seconds": 1}
# each traffic kind's end-to-end metrics: unit and which way is better
E2E = {"train": {"train_samples_per_s": ("samples/s", "higher")}}


def copy_bench(dst: Path) -> Path:
    """``BENCHMARK.json`` and ``bench/`` copied under ``dst``."""
    shutil.copy(ROOT / "BENCHMARK.json", dst / "BENCHMARK.json")
    shutil.copytree(ROOT / "bench", dst / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    return dst


def add_cell(root: Path, name: str, config: dict, traffic_name: str,
             traffic: dict, limits: dict, chips: int = 1) -> None:
    """Add one cell as new files and appended entries: the cell, and
    its kind's end-to-end metrics where the benchmark has none yet."""
    d = root / "bench"
    (d / "configs" / f"{config['name']}.json").write_text(json.dumps(config))
    (d / "traffic" / f"{traffic_name}.json").write_text(json.dumps(traffic))
    (d / "limits" / f"{name}.json").write_text(json.dumps(limits))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["workloads"].append({"name": name, "config": config["name"],
                               "traffic": traffic_name, "chips": chips,
                               "why": "tiny"})
    names = E2E[traffic["kind"]]
    for m in bench["end_to_end"]:
        if m["name"] in names:
            m["workloads"].append(name)
    bench["end_to_end"] += [
        {"name": n, "unit": u, "better": b, "bound": 0.1,
         "source": "host_clock", "workloads": [name]}
        for n, (u, b) in names.items()
        if n not in {m["name"] for m in bench["end_to_end"]}]
    (root / "BENCHMARK.json").write_text(json.dumps(bench))


def limits(cell: str) -> dict:
    return json.loads((ROOT / "bench" / "limits" / f"{cell}.json")
                      .read_text())


def tiny_root(dst: Path) -> Path:
    """A benchmark copy with ``tiny.train``, whose limits are those of the
    chip cell ``wdl-s1.esd.1c``."""
    root = copy_bench(dst)
    add_cell(root, "tiny.train", TINY, "tiny.train", TRAIN,
             limits("wdl-s1.esd.1c"))
    return root


CPU = {"platform": "cpu", "kind": "TPU v5 lite", "count": 1}
