#!/bin/bash
# Analysis smoke: the tpu_als/analysis subsystem's CI gate, CPU-only.
# Two stages, fail-fast:
#
#   1. the tracer-safety lint over the default roots, PROVEN jax-free:
#      the linter runs under a poisoned `jax` module (an import raises,
#      the tests/test_regress.py discipline), so a jax import creeping
#      into the stdlib-only stage 1 fails here, not in a jax-less CI
#      container.  The checked-in baseline (lint_baseline.txt) is
#      policy-EMPTY, so any finding is a failure.
#   2. the jaxpr contract registry — the named byte pins (ne_audit,
#      fused_solve_audit, guardrails_disarmed, tracing_disarmed,
#      plan_cache_off, comm_audit, ring_substrate, live_delta_index,
#      elastic_disarmed, floor_audit) re-verified
#      through the real CLI on an 8-device CPU backend.  floor_audit is
#      a bank pin, not a jaxpr pin: the committed BENCH_autotune_cpu.json
#      must keep tuned <= default and measured-vs-modeled inside its
#      band (TPU_ALS_FLOOR_BAND), so the roofline gap cannot silently
#      reopen.
#
# Usage: scripts/lint_smoke.sh   (from the repo root; ~1 min on CPU)
set -u

cd "$(dirname "$0")/.."
fail=0

echo "== lint smoke 1/2: tracer-safety lint (poisoned jax) =="
poison=$(mktemp -d)
trap 'rm -rf "$poison"' EXIT
cat >"$poison/jax.py" <<'EOF'
raise ImportError("poisoned: the stdlib-only lint stage imported jax")
EOF
PYTHONPATH="$poison" python tpu_als/analysis/lint.py || fail=1

echo "== lint smoke 2/2: jaxpr contract registry =="
JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    python -m tpu_als.cli lint --paths tpu_als/analysis --contracts \
    || fail=1

if [ "$fail" -ne 0 ]; then
    echo "lint smoke: FAIL" >&2
    exit 1
fi
echo "lint smoke: OK"
