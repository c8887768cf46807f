"""Quantized-exchange benchmark: loss vs wire bitwidth.

Trains the DLRM driver end-to-end once per codec (fp32 / fp16 / int8 /
int4) on the same stream and seed, recording the loss curve plus the
simulator's wire-byte census for the matching codec — the trade the
paper's edge setting cares about: how many bytes each embedding
transmission costs vs how much the quantization noise moves the loss.
Embedding gradients ride up with error feedback (the residual carries
what each step's quantizer dropped) and table rows ride down through a
straight-through estimator, so every codec trains the same graph.

Writes benchmarks/results/BENCH_quant.json.  ``--quick`` runs the
[none, int8] pair for a few steps into BENCH_quant_quick.json
(untracked) and doubles as the CI smoke: it asserts every loss is
finite, that training still learns under int8, and that the int8 census
shows >= 4x fewer wire bytes than fp32.
"""
from __future__ import annotations

from pathlib import Path

import numpy as np

from repro.launch import train
from repro.obs import write_bench


def _run_driver(codec: str | None, steps: int) -> list[dict]:
    """One driver run in this process (on whatever backend JAX picked —
    a child process could not share the accelerator) -> per-step
    records."""
    argv = ["--arch", "wdl-tiny", "--steps", str(steps),
            "--batch-per-worker", "16", "--log-every", "1", "--seed", "0"]
    if codec is not None:
        argv += ["--codec", codec]
    return train.main(argv)


def _census(codec: str | None) -> dict | None:
    """Simulator byte census for the codec on a small Zipf stream."""
    from repro.core import SimConfig, simulate
    from repro.data.synthetic import CTRWorkload

    wl = CTRWorkload(name="zipf1.2", model="wdl",
                     table_sizes=(20_000,) * 4 + (1_000,) * 8,
                     zipf_a=(1.2,) * 12, hist_max=8, hist_mean=4.0)
    r = simulate(SimConfig(workload=wl, n_workers=8, batch_per_worker=32,
                           cache_ratio=0.05, embedding_dim=64, iters=8,
                           warmup=2, mechanism="esd", alpha=1.0,
                           codec=codec))
    return r.quant


def run(quick: bool = False, out: Path | None = None) -> dict:
    codecs = [None, "int8"] if quick else [None, "fp16", "int8", "int4"]
    steps = 8 if quick else 40
    report = {"config": {"arch": "wdl-tiny", "steps": steps,
                         "batch_per_worker": 16, "seed": 0},
              "results": {}}
    for codec in codecs:
        name = codec or "fp32"
        recs = _run_driver(codec, steps)
        losses = [r["loss"] for r in recs]
        assert losses and all(np.isfinite(losses)), (name, losses)
        census = _census(codec)
        row = {"losses": losses, "final_loss": losses[-1],
               "quant": census}
        report["results"][name] = row
        red = census["byte_reduction"] if census else 1.0
        print(f"quant.{name},{losses[-1] * 1e4:.0f},"
              f"final_loss={losses[-1]:.4f},byte_red={red:.1f}x")

    fp32 = report["results"]["fp32"]
    for name, row in report["results"].items():
        if name == "fp32":
            continue
        # quantization noise must not stop learning on this stream
        assert row["losses"][-1] < row["losses"][0], name
        assert row["quant"]["byte_reduction"] >= 2.0, name
    if "int8" in report["results"]:
        assert report["results"]["int8"]["quant"]["byte_reduction"] >= 4.0
    assert fp32["losses"][-1] < fp32["losses"][0]

    write_bench("quant", report, quick=quick, out=out)
    return report


if __name__ == "__main__":
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    args = ap.parse_args()
    run(quick=args.quick)
