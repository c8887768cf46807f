#!/usr/bin/env python3
"""Run one cell of the benchmark on the chips of this machine.

    python bench/run.py --workload wdl-s1.esd.1c --seed 7 --seconds 30 --trace 0

The cell is looked up by name in ``BENCHMARK.json`` at the root of the
checkout; its configuration (``bench/configs/<config>.json``), traffic
mix (``bench/traffic/<traffic>.json``), limits of the check
(``bench/limits/<workload>.json``), model kind
(``bench/models/<kind>.py``, the configuration's ``kind``) and, with
``--trace 1``, its per-layer metrics (``bench/metrics/<metric>.py``)
are found by name.
The traffic mix's ``kind`` names the driver (``train``).

The last line of stdout is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics`` (the cell's end-to-end metrics with
``--trace 0``, its per-layer metrics with ``--trace 1``), ``device``,
``breakdown`` with ``--trace 1``, and last ``checks``: every number
compared with the reference beside its limit, which also close stderr.
Without a TPU, or with another number of chips than the cell asks
for, it exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import traceback  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent


class Cell:
    """One entry of ``workloads`` with the files it names."""

    def __init__(self, root: Path, name: str):
        bench = json.loads((root / "BENCHMARK.json").read_text())
        cells = {w["name"]: w for w in bench["workloads"]}
        if name not in cells:
            raise SystemExit(f"no workload {name!r} in BENCHMARK.json")
        self.entry = cells[name]
        self.name = name
        self.root = root
        self.chips = int(self.entry["chips"])
        d = root / "bench"
        self.config = _load(d / "configs" / f"{self.entry['config']}.json")
        self.traffic = _load(d / "traffic" / f"{self.entry['traffic']}.json")
        self.limits = _load(d / "limits" / f"{name}.json")
        self.per_layer = [m for m in bench["per_layer"]
                          if name in m.get("workloads", (name,))]
        self.metric_files = {m["name"]: d / "metrics" / f"{m['name']}.py"
                             for m in self.per_layer}
        for path in self.metric_files.values():
            if not path.is_file():
                raise SystemExit(f"missing metric reader {path}")
        self.end_to_end = [m for m in bench["end_to_end"]
                           if name in m.get("workloads", (name,))]


def _load(path: Path) -> dict:
    if not path.is_file():
        raise SystemExit(f"missing {path}")
    return json.loads(path.read_text())


def read_metric(path: Path, ctx: dict):
    """Run the reader in ``path`` on ``ctx``; None where it finds nothing."""
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + path.stem.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(ctx)


def find_device(chips: int) -> dict:
    import jax

    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    if device["platform"] != "tpu":
        raise SystemExit(f"bench: needs a TPU; JAX found {device}")
    if device["count"] != chips:
        raise SystemExit(f"bench: the cell asks for {chips} chips; JAX "
                         f"found {device}")
    return device


def use_cache(root: Path) -> None:
    """JAX's persistent compilation cache at a fixed path in the
    checkout, for every program however short its compile."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(root / ".jax_cache")
    import jax

    jax.config.update("jax_compilation_cache_dir", str(root / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             device: dict, t_start: float = T_START) -> dict:
    """Drive the cell and judge it; returns the result object."""
    from bench import checks, train_cell

    drivers = {"train": train_cell.run}
    kind = cell.traffic["kind"]
    if kind not in drivers:
        raise SystemExit(f"unknown traffic kind {kind!r}")
    try:
        out = drivers[kind](cell.entry["config"], cell.config, cell.traffic,
                            seed, seconds, trace, device, root=cell.root)
    except Exception:
        traceback.print_exc()
        return {"correct": False, "attempted": 0, "failed": 0, "metrics": {},
                "device": dict(device, memory_peak_bytes=0),
                "checks": {"run_raised": {"value": 1, "limit": 0}}}
    correct, shown = checks.judge(out["checks"], cell.limits)
    dev = dict(device, memory_peak_bytes=out["memory_peak_bytes"])
    result = {"correct": correct, "attempted": out["attempted"],
              "failed": out["failed"]}
    if trace:
        red = out["trace"]["reduced"]
        ctx = dict(out["trace"], device=device)
        metrics = {}
        for m in cell.per_layer:
            value = read_metric(cell.metric_files[m["name"]], ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        dev.update(busy_s=red.busy_s, window_s=red.window_s)
        result.update(metrics=metrics, device=dev, breakdown={
            "device_ops": [[n, s] for n, s in red.top_ops(10)],
            "idle_gaps": [[n, s] for n, s in red.gaps[:10]]})
    else:
        metrics = {k: {"value": v, "unit": _unit(cell, k)}
                   for k, v in out["metrics"].items()}
        metrics["setup_s"] = {"value": out["setup_end"] - t_start,
                              "unit": "s"}
        result.update(metrics=metrics, device=dev)
    result["checks"] = shown
    other = {k: v for k, v in out["checks"].items() if k not in shown}
    if other:
        print(f"reported, not compared: {_plain(other)}", file=sys.stderr)
    return result


def _unit(cell: Cell, name: str) -> str:
    return next(m["unit"] for m in cell.end_to_end if m["name"] == name)


def _plain(x):
    """JSON has no NaN or infinity: such a value is written as a string."""
    if isinstance(x, float) and not math.isfinite(x):
        return str(x)
    if isinstance(x, dict):
        return {k: _plain(v) for k, v in x.items()}
    if isinstance(x, (list, tuple)):
        return [_plain(v) for v in x]
    return x


def report(result: dict) -> None:
    for name, c in result["checks"].items():
        print(f"check {name} {c['value']} limit {c['limit']}",
              file=sys.stderr)
    print(json.dumps(_plain(result)), flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = Cell(ROOT, args.workload)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    use_cache(ROOT)
    device = find_device(cell.chips)
    report(run_cell(cell, args.seed, args.seconds, bool(args.trace), device))
    return 0


if __name__ == "__main__":
    sys.exit(main())
