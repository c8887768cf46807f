#!/usr/bin/env python3
"""Readings that the limits of ``bench/limits/<workload>.json`` are set
from, in one process on the chips of this machine.

    python bench/calibrate.py --workload wdl-s1.esd.1c --seeds 1 2 3 \\
        --control-seeds 4 5 6 --seconds 2

For each of ``--seeds`` it drives a whole run of the cell (with a short
window) and prints every number its check computes.  For each of
``--control-seeds`` it prints the same numbers for the controls, the
reference put in the program's place below the precision the
configuration states (matmul operands rounded to fp8; everything stored
and computed in bfloat16), and for the faults the cell can have,
planted in the reference: half of each batch left out with the mean
taken over the rest (a step that returns its state unchanged reads 1 on
the change of the parameters by construction).  Every line is judged
against the cell's limits as a run is, and says whether it is
``correct``: a control or a fault has to read false.  One JSON line per
reading on stdout.  The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import run  # noqa: E402


def controls(cell, seed: int) -> list[dict]:
    """The controls' and faults' numbers on the cell's first three
    batches of ``seed``, each beside whether the cell's limits pass it."""
    import jax.numpy as jnp

    from bench import checks, reference, traffic

    model = reference.Model(cell.config, cell.root)
    t = cell.traffic
    k = int(t["batch_per_worker"]) * cell.chips
    sampler = traffic.sampler(cell.config, cell.root)
    batches = [b for _, b in zip(range(3), sampler.batches(seed + 1, k))]
    lr = float(t["lr"])
    ref = reference.train_steps(model, seed, batches, lr)
    out = []
    for kind, kw in (("control_fp8", {"operands": jnp.float8_e4m3fn}),
                     ("control_bf16", {"dtype": jnp.bfloat16}),
                     ("fault_half_batch", {"keep": 0.5})):
        got = reference.train_steps(model, seed, batches, lr, **kw)
        nums = checks.train_numbers(got, ref)
        correct, _ = checks.judge(nums, cell.limits)
        out.append({"kind": kind, "seed": seed, "correct": correct, **nums})
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="*", default=[])
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--seconds", type=float, default=2.0)
    args = ap.parse_args(argv)
    cell = run.Cell(ROOT, args.workload)
    run.use_cache(ROOT)
    device = run.find_device(cell.chips)
    from bench import checks, train_cell

    for seed in args.seeds:
        out = train_cell.run(cell.entry["config"], cell.config, cell.traffic,
                             seed, args.seconds, False, device,
                             root=cell.root)
        correct, _ = checks.judge(out["checks"], cell.limits)
        print(json.dumps(run._plain({
            "kind": "program", "seed": seed, "correct": correct,
            "samples_per_s": out["metrics"]["train_samples_per_s"],
            "memory_peak_bytes": out["memory_peak_bytes"],
            **out["checks"]})), flush=True)
    for seed in args.control_seeds:
        for line in controls(cell, seed):
            print(json.dumps(run._plain(line)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
