#!/usr/bin/env bash
# CI entry point: tier-1 tests + backend-parity smoke + stage-1 trajectory.
#
# Off-TPU every Pallas kernel body runs in interpret mode, so the fused
# scan+top-L (and every other kernel) is exercised on CPU-only runners; on
# a TPU the kernels always compile. The on-chip check is chip_smoke.py.
set -euo pipefail
cd "$(dirname "$0")"

export PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH}

echo "== static analysis (HLO contracts + repo lint + compile discipline) =="
python -m repro.analysis.check
# the gate must also be able to FAIL: on the seeded-violation fixtures
# (oracle-less kernel, recompile hazards, a materialized (Q, N) scan) a
# zero exit means the detectors went blind
if python -m repro.analysis.check --seeded-violations > /dev/null 2>&1; then
  echo "ERROR: --seeded-violations exited 0 (detectors missed seeded defects)"
  exit 1
fi
echo "seeded-violation fixtures correctly rejected"
if command -v ruff > /dev/null 2>&1; then
  ruff check src tests benchmarks
else
  echo "(ruff not installed in this container; baseline lives in pyproject.toml)"
fi

echo "== tier-1 tests (docs suite runs in its own gate below) =="
python -m pytest -x -q --ignore=tests/test_docs.py

echo "== docs gate (snippet tests + dead intra-repo links) =="
python -m pytest -q tests/test_docs.py

echo "== autotuner quick sweep (self-checks + cache roundtrip, tmp cache) =="
# --quick sweeps one small bucket per engine kernel into a THROWAWAY cache
# path: proves the sweep driver, the determinism/schema self-checks and the
# cache I/O on every PR without touching the committed TUNE_CACHE.json
REPRO_TUNE_CACHE="$(mktemp -d)/tune_cache.json" python -m repro.tune --quick

echo "== backend-parity smoke (all scan backends vs xla oracle) =="
python -m benchmarks.run --smoke

echo "== stage-1 engine trajectory (writes BENCH_stage1.json) =="
python -m benchmarks.run --only stage1 --scale quick

echo "== stage-2 engine trajectory (writes BENCH_stage2.json) =="
python -m benchmarks.run --only stage2 --scale quick

echo "== IVF trajectory: nprobe dial + residual study (writes BENCH_ivf.json) =="
python -m benchmarks.run --only ivf --scale quick

echo "== serving smoke (batched-vs-solo parity + zero deadline misses) =="
# deterministic trace through repro.serve on flat + IVF indexes; exits
# non-zero if any batched request drifts bit-wise from searching it
# alone, or if any generously-deadlined request misses
python -m repro.serve --smoke

echo "== serving trajectory: latency under load (writes BENCH_serve.json) =="
python -m benchmarks.run --only serve --scale quick

echo "CI OK"
