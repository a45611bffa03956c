#!/usr/bin/env bash
# Tier-1 CI entrypoint.
#
#   scripts/ci.sh          — the ROADMAP.md tier-1 command (full suite)
#   scripts/ci.sh fast     — fast path: lint + skip @slow jit/model tests
#   scripts/ci.sh lint     — static analysis only (jaxlint + plancheck
#                            smoke; `make lint`)
#
# Needs the packages in requirements-dev.txt (jax, numpy, pytest,
# hypothesis).
set -euo pipefail
cd "$(dirname "$0")/.."

export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"

if [[ "${1:-}" == "lint" || "${1:-}" == "fast" ]]; then
    # static analysis: jaxlint (JAX001..006) must be clean over src/, and
    # one SSM plan per strategy must pass the plancheck catalog
    # (PLN001..006) — see src/repro/analysis/
    python -m repro.analysis.jaxlint src/repro
    python scripts/lint_plans.py
fi
if [[ "${1:-}" == "lint" ]]; then
    exit 0
fi

if [[ "${1:-}" == "fast" ]]; then
    python -m pytest -x -q -m "not slow"
    # closed-loop controller must beat always/never-migrate, and the
    # refreshed BENCH json must match the committed baselines
    python -m benchmarks.fig13_controller
    python scripts/check_bench.py BENCH_controller.json
    # five-strategy migration frontier at smoke scale: batched_fluid must
    # beat fluid on total migration time at fluid's tail latency
    python -m benchmarks.fig12_fluid_vs_progressive --smoke
    python scripts/check_bench.py BENCH_fig12_smoke.json
    # real-state serving resize: the live elastic event must move the
    # actual KV cache bit-identically (tokens match a no-resize run)
    python -m benchmarks.fig14_serving_resize --smoke
    python scripts/check_bench.py BENCH_serving_smoke.json
    # differential gate: every SSM solver (brute/simple/numpy/jit) must
    # agree on feasibility and optimal gain across the randomized stream
    exec python -m benchmarks.ssm_oracles
fi
exec python -m pytest -x -q
