#!/usr/bin/env python3
"""Smoke run of the DLRM + ESD main path on TPU, through its entry points.

    python chip_smoke.py              # one chip: train, serve, kernels
    python chip_smoke.py --chips 4    # four chips: ESD vs plain training

Phases, all at the paper's full width (``wdl-s1``: E = 512, MLP
1024-512-256, S1 table of 502k rows, ~1.03 GB f32), with random weights
from ``--seed``:

  train    ``repro.launch.train.main`` with ESD (alpha 1, ragged exchange,
           pipeline depth 2, lookahead 4, prefetch 64), then the same
           stream and seed without ESD (plain data parallel) on the same
           mesh.  Every step's loss is finite, miss_pull and cost are
           logged, the exchange overflows nothing, and the two loss
           curves agree within fp32 tolerance over the first steps (the
           loss is a batch mean, and dispatch only moves samples between
           workers).
  serve    ``repro.launch.serve`` with ``--use-pallas``: every request of
           the stream is answered and p99 latency is finite.
  kernels  every main-path Pallas kernel compiled (``interpret=False``)
           at E = 512 and checked against ``repro.kernels.ref``.

``--chips 4`` runs the train phase alone, over a 4-device mesh: the only
part of the main path that exists across chips (shard_map, all_to_all
and the row-sharded table).

Earlier stdout lines are JSON smoke readings (compile seconds, step
times, peak device memory), not benchmarks.  The last line is
``{"ok": true, "device": {...}}``.  Without a TPU the script exits
nonzero and prints no result.  It runs in one process and starts none.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

ARCH = "wdl-s1"
ESD_FLAGS = ["--esd-alpha", "1", "--exchange", "ragged",
             "--pipeline-depth", "2", "--lookahead", "4", "--prefetch", "64"]


def say(phase: str, **fields) -> None:
    """One smoke reading: a JSON line on stdout."""
    print(json.dumps({"phase": phase, **fields}), flush=True)


def peak_bytes_in_use():
    import jax

    stats = jax.devices()[0].memory_stats()
    return None if stats is None else stats.get("peak_bytes_in_use")


def phase_train(arch: str = ARCH, steps: int = 8, batch_per_worker: int = 128,
                seed: int = 0, esd: bool = True) -> list[float]:
    """Train ``steps`` steps through ``repro.launch.train.main``; returns
    the loss curve after checking the per-step records."""
    from repro.launch import train

    argv = ["--arch", arch, "--steps", str(steps), "--batch-per-worker",
            str(batch_per_worker), "--seed", str(seed),
            "--log-every", str(steps)]
    t0 = time.perf_counter()
    recs = train.main(argv + (ESD_FLAGS if esd else []))
    wall = time.perf_counter() - t0
    losses = [r["loss"] for r in recs]
    if len(losses) != steps or not np.all(np.isfinite(losses)):
        raise AssertionError(f"train (esd={esd}): losses {losses}")
    if esd:
        for r in recs:
            missing = {"miss_pull", "cost", "exchange_overflow"} - r.keys()
            if missing:
                raise AssertionError(f"step {r['step']} lacks {missing}")
            if r["exchange_overflow"] != 0:
                raise AssertionError(f"step {r['step']}: exchange dropped "
                                     f"{r['exchange_overflow']} rows")
            bad = {k: v for k, v in r.items()
                   if isinstance(v, float) and not np.isfinite(v)}
            if bad:
                raise AssertionError(f"step {r['step']}: non-finite {bad}")
    say("train", esd=esd, losses=losses,
        miss_pull=[r.get("miss_pull") for r in recs],
        first_step_s_with_compile=recs[0]["wall_s"],
        median_later_step_s=float(np.median([r["wall_s"]
                                             for r in recs[1:]])),
        wall_s=wall, peak_bytes_in_use=peak_bytes_in_use())
    return losses


def phase_parity(esd_losses, plain_losses, rtol: float = 1e-5,
                 first: int = 2) -> None:
    """ESD and plain data-parallel training see the same samples each
    step, so their batch-mean losses agree to fp32 rounding over the
    ``first`` steps: step 0 on equal parameters (a wrongly routed sample
    would move it by ~1/k), step 1 after one update (a wrong gradient
    would).  Across devices the two sum in different orders, and later
    steps amplify that rounding (the default learning rate spikes the
    loss at full width), so the rest of the curve is reported, not held
    to fp32."""
    esd, plain = np.asarray(esd_losses), np.asarray(plain_losses)
    rel = np.abs(esd - plain) / np.abs(plain)
    say("parity", rel_diff=rel.tolist(), first=first, rtol=rtol)
    if not np.all(rel[:first] <= rtol):
        raise AssertionError(f"ESD vs plain loss curves differ by "
                             f"{rel[:first].tolist()} in the first {first} "
                             f"steps: {esd.tolist()} vs {plain.tolist()}")


def phase_serve(arch: str = ARCH, qps: float = 60.0, duration: float = 1.0,
                seed: int = 0) -> dict:
    """Serve a seeded request stream through ``repro.launch.serve`` with
    the Pallas staged-read kernels."""
    from repro.launch import serve

    out = serve.main(["--arch", arch, "--qps", str(qps), "--duration",
                      str(duration), "--seed", str(seed), "--use-pallas"])
    if out["n_arrivals"] == 0 or out["n_requests"] != out["n_arrivals"]:
        raise AssertionError(f"served {out['n_requests']} of "
                             f"{out['n_arrivals']} requests")
    if not np.isfinite(out["p99_ms"]):
        raise AssertionError(f"p99 {out['p99_ms']}")
    say("serve", n_requests=out["n_requests"], p50_ms=out["p50_ms"],
        p99_ms=out["p99_ms"], peak_bytes_in_use=peak_bytes_in_use())
    return out


def _check(name, fn, args, kw, want, tols=(None,)):
    """Compile ``fn`` for ``args``, run it and compare each output with
    ``want``: a tolerance of None demands bitwise equality, else
    ``(rtol, atol)``."""
    t0 = time.perf_counter()
    compiled = fn.lower(*args, **kw).compile()
    compile_s = time.perf_counter() - t0
    got = compiled(*args)
    outs = got if isinstance(got, tuple) else (got,)
    wants = want if isinstance(want, tuple) else (want,)
    errs = []
    for g, w, tol in zip(outs, wants, tols, strict=True):
        g, w = np.asarray(g), np.asarray(w)
        if g.shape != w.shape:
            raise AssertionError(f"{name}: shape {g.shape} != {w.shape}")
        errs.append(float(np.max(np.abs(g - w))) if g.size else 0.0)
        ok = (np.array_equal(g, w) if tol is None
              else np.allclose(g, w, rtol=tol[0], atol=tol[1]))
        if not ok:
            raise AssertionError(f"{name}: max |err| {errs[-1]} (tol {tol})")
    say("kernel", name=name, compile_s=compile_s, max_abs_err=errs,
        tolerance=tols)


def phase_kernels(E: int = 512, V: int | None = None, B: int = 256,
                  F: int = 26, seed: int = 0, interpret: bool = False) -> None:
    """Each main-path kernel against its ``repro.kernels.ref`` oracle.
    Row counts off the 8-row DMA tile (the staging plane, the sample
    rows) exercise the partial-tail path."""
    import jax
    import jax.numpy as jnp

    from repro.data.synthetic import WORKLOADS
    from repro.kernels import ref
    from repro.kernels.emb_lookup import (pooled_lookup, pooled_lookup_quant,
                                          pooled_lookup_staged, staged_gather)
    from repro.kernels.exchange_pack import (gather_rows_pallas,
                                             gather_rows_quant_pallas)
    from repro.quant.codecs import quantize_rows

    V = WORKLOADS["S1"].vocab if V is None else V
    rng = np.random.default_rng(seed)
    kw = {"interpret": interpret}

    def ids_for(rows, shape, pad=0.2):
        out = rng.integers(0, rows, shape)
        out[rng.random(shape) < pad] = -1
        out.flat[:2] = (rows - 1, 0)                # last and first tile
        return jnp.asarray(out, jnp.int32)

    table = jax.random.normal(jax.random.key(seed), (V, E), jnp.float32)
    ids = ids_for(V, (B, F))
    w = jnp.asarray(rng.random((B, F)), jnp.float32)
    pooled = ((1e-5, 1e-5),)          # summation order / fused multiply-add
    want = ref.pooled_lookup_ref(table, ids, w)
    _check("pooled_lookup", pooled_lookup, (table, ids, w), kw, want, pooled)
    _check("pooled_lookup_block_f", pooled_lookup, (table, ids, w),
           dict(kw, block_f=8), want, pooled)

    C = 512
    plane = jnp.asarray(rng.standard_normal((C, E)), jnp.float32)
    src = jnp.asarray(np.where(rng.random(C) < 0.5,
                               rng.integers(0, V, C), -1), jnp.int32)
    src = src.at[0].set(V - 1)
    _check("staged_gather", staged_gather, (plane, table, src), kw,
           ref.staged_gather_ref(plane, table, src))

    C = V // 4 + 3                                  # off the 8-row tile
    big_plane = jax.random.normal(jax.random.key(seed + 1), (C, E),
                                  jnp.float32)
    hist = ids_for(V, (16, WORKLOADS["S1"].hist_max), pad=0.5)
    slots = jnp.where((hist >= 0) & (rng.random(hist.shape) < 0.5),
                      ids_for(C, hist.shape, pad=0.0), -1)
    _check("pooled_lookup_staged", pooled_lookup_staged,
           (big_plane, table, slots, hist), kw,
           ref.pooled_lookup_staged_ref(big_plane, table, slots, hist), pooled)

    codec = "int8:32"
    codes, scale, zp = quantize_rows(table, codec)
    _check("pooled_lookup_quant", pooled_lookup_quant,
           (codes, scale, zp, ids), dict(kw, codec=codec),
           ref.pooled_lookup_quant_ref(codes, scale, zp, ids, codec), pooled)

    m = 130                                         # off the 8-row tile
    rows = jnp.asarray(rng.standard_normal((m, E)), jnp.float32)
    sample_ids = jnp.asarray(rng.integers(0, V, (m, WORKLOADS["S1"].width)),
                             jnp.int32)
    slot_to_row = ids_for(m, (128,))
    _check("gather_rows_pallas", gather_rows_pallas, (rows, slot_to_row),
           kw, ref.gather_rows_ref(rows, slot_to_row))
    _check("gather_rows_pallas_ids", gather_rows_pallas,
           (sample_ids, slot_to_row), kw,
           ref.gather_rows_ref(sample_ids, slot_to_row))
    # zp exact; scale within an ULP of the (hi - lo) / levels division,
    # which can move a boundary code by one
    _check("gather_rows_quant_pallas", gather_rows_quant_pallas,
           (rows, slot_to_row), dict(kw, codec=codec),
           ref.gather_rows_quant_ref(rows, slot_to_row, codec),
           ((0, 1.0), (1e-6, 0), None))
    say("kernels", peak_bytes_in_use=peak_bytes_in_use())


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: train + serve + kernels on one chip; 4: ESD "
                         "vs plain training over a 4-device mesh only")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    if device["platform"] != "tpu":
        sys.exit(f"chip_smoke: needs a TPU; JAX found {device}")
    if device["count"] != args.chips:
        sys.exit(f"chip_smoke: --chips {args.chips} needs exactly that many "
                 f"devices; JAX found {device}")

    from repro.launch.cache import use_compile_cache

    say("device", **device, compile_cache=str(use_compile_cache()))
    esd = phase_train(seed=args.seed, esd=True)
    plain = phase_train(seed=args.seed, esd=False)
    phase_parity(esd, plain)
    if args.chips == 1:
        phase_serve(seed=args.seed)
        phase_kernels(seed=args.seed)
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()
