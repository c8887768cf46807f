#!/usr/bin/env bash
# One-command verify recipe: fast pre-test gate (compileall + quickstart
# smoke), tier-1 tests, kernel and dispatch benchmark smoke.
#
#   scripts/ci.sh              # tier-1 (full suite, default selection) + bench smoke
#   scripts/ci.sh --slow       # also run the @slow paper-scale tests
#
# Wall-time notes: the suite is jit-bound, so CI (a) disables the
# expensive LLVM passes (the compiled programs run for microseconds;
# correctness-neutral — no fast-math) and (b) keeps a persistent XLA
# compilation cache so reruns only pay tracing.
set -euo pipefail
cd "$(dirname "$0")/.."
export PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH}
# tier-1 is a CPU suite; never pay (or hang on) accelerator-driver init
export JAX_PLATFORMS=${JAX_PLATFORMS:-cpu}

# Compile-speed env for the TEST runs only (the compiled programs run for
# microseconds, so skipping the expensive LLVM passes is a pure win and
# correctness-neutral — no fast-math).  The bench smoke below must NOT
# inherit these: it measures runtime.
TEST_ENV=(
  "XLA_FLAGS=--xla_backend_optimization_level=0 --xla_llvm_disable_expensive_passes=true${XLA_FLAGS:+ $XLA_FLAGS}"
  "JAX_COMPILATION_CACHE_DIR=${JAX_COMPILATION_CACHE_DIR:-$PWD/.jax_cache}"
  "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS=0.2"
)

RUN_SLOW=0
for arg in "$@"; do
  [ "$arg" = "--slow" ] && RUN_SLOW=1
done

# fast pre-test gate: import-time/syntax breakage fails in seconds, not
# mid-suite — byte-compile every tree we ship, one end-to-end quickstart
# pass (exercises core cost/dispatch/cache on a real batch), the quick
# ragged-exchange sweep (plan bytes + slack Alg.-1 drop), the quick
# pipeline sweep (decision hiding + lookahead miss reduction + the
# prefetch W x depth grid and W=0-vs-W=8 driver demand-miss acceptance
# run against the Belady bound) and the
# quick elastic sweep (fault-injection smoke: crash + rejoin must keep
# >= 70% of oracle throughput with finite stats); the quick sweeps write
# *_quick.json artifacts, never the tracked full-sweep records
t0=$SECONDS
python -m compileall -q src benchmarks examples tests
python examples/quickstart.py > /dev/null
python -m benchmarks.dispatch_bench --exchange --quick
python -m benchmarks.pipeline_bench --quick
python -m benchmarks.elastic_bench --quick
# quantized-exchange smoke: fp32 vs int8 driver runs must both learn and
# the int8 census must show >= 4x fewer wire bytes
python -m benchmarks.quant_bench --quick
# serving smoke: the virtual-clock serve episodes (Poisson stream +
# flash-crowd burst) must report finite p99 and ESD must beat random on
# both p99 latency and SLO-violation rate at the reference QPS
python -m benchmarks.serve_bench --quick
# every BENCH_*.json (tracked full sweeps AND the quick artifacts the
# gate just wrote) must satisfy the shared schema gates
python scripts/bench_check.py
# traced driver smoke: a real pipelined run must export a valid Chrome
# trace and print the top-10 slowest spans (stderr)
python -m repro.launch.train --arch wdl-tiny --steps 8 \
  --batch-per-worker 8 --esd-alpha 1 --pipeline-depth 2 --lookahead 8 \
  --prefetch 16 --exchange ragged \
  --trace-out benchmarks/results/ci_trace_quick.json > /dev/null
python - benchmarks/results/ci_trace_quick.json <<'EOF'
import json, sys
doc = json.load(open(sys.argv[1]))
assert doc["traceEvents"], "empty trace"
EOF
echo "pre-test gate (compileall + quickstart + exchange/pipeline/elastic/quant smoke + bench schema check + traced driver): $((SECONDS - t0))s"

t0=$SECONDS
env "${TEST_ENV[@]}" python -m pytest -q --durations=10
echo "tier-1 wall: $((SECONDS - t0))s (persistent compile cache + reduced LLVM opt)"

if [ "$RUN_SLOW" = 1 ]; then
  env "${TEST_ENV[@]}" python -m pytest -q --durations=10 -m slow
fi

# bench smoke: kernels (interpret mode) + dispatch-step dense-vs-sparse
python -m benchmarks.run --quick --only kernels,dispatch
echo "ci.sh: OK"
