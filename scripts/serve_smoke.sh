#!/bin/bash
# Serving smoke: the online-serving subsystem's CI gate, CPU-only (no
# accelerator, no network).  Four stages, fail-fast:
#
#   1. the serving test tier — int8-index bitwise property sweep,
#      admission queue, engine loop, serving fault points, and the
#      topk validity mask (tests/test_serving.py + the topk/sharded
#      companions),
#   2. the static checks — the obs-schema shim (the serving.* metric
#      vocabulary and the serving_publish event must stay declared)
#      plus the analysis gate (scripts/lint_smoke.sh: poisoned-jax
#      tracer-safety lint + the jaxpr contract registry),
#   3. one END-TO-END open-loop serve-bench: 5 seconds of synthetic
#      load on CPU against a loose SLO, the result banked with
#      banked_at provenance and sanity-checked (non-empty histograms,
#      SLO met, nothing shed),
#   4. one SHARDED serve-bench on the 8-device forced-host mesh: the
#      catalog placed shard-resident, the sharded int8 backend
#      scoring, sanity-checked the same way plus the resolved backend
#      and the traffic-derived bucket ladder,
#   5. the bench regression gate over the committed result banks
#      (scripts/bench_gate.sh — regressions, null banks, missing
#      provenance all exit non-zero).
#
# Usage: scripts/serve_smoke.sh   (from the repo root; ~2 min on CPU)
set -u

cd "$(dirname "$0")/.."
export JAX_PLATFORMS=cpu
fail=0

echo "== serve smoke 1/5: serving test tier =="
python -m pytest tests/test_serving.py tests/test_serve_sharded.py \
    tests/test_serve_fabric.py \
    tests/test_topk_foldin.py -q -m 'not slow' -p no:cacheprovider || fail=1

echo "== serve smoke 2/5: static checks (obs schema + analysis gate) =="
python scripts/check_obs_schema.py || fail=1
scripts/lint_smoke.sh || fail=1

echo "== serve smoke 3/5: end-to-end open-loop serve-bench =="
work=$(mktemp -d)
trap 'rm -rf "$work"' EXIT
python -m tpu_als.cli serve-bench \
    --users 2000 --items 5000 --rank 32 --k 10 --shortlist-k 64 \
    --qps 100 --duration 5 --slo-ms 2000 --max-wait-ms 2 \
    --bench-json "$work/BENCH_serve_smoke.json" \
    >"$work/serve.out" 2>"$work/serve.log"
rc=$?
if [ "$rc" -ne 0 ]; then
    echo "FAIL: serve-bench exited $rc" >&2
    tail -5 "$work/serve.log" >&2
    fail=1
else
    python - "$work/BENCH_serve_smoke.json" <<'EOF' || fail=1
import json, sys

r = json.load(open(sys.argv[1]))
problems = []
if r["metric"] != "serve_e2e_p99_ms":
    problems.append(f"unexpected metric {r['metric']!r}")
if not r["scored"]:
    problems.append("no request completed (empty latency histograms)")
if not r["slo_met"]:
    problems.append(f"p99 {r['value']}ms blew the loose {r['slo_ms']}ms SLO")
if r["shed_rate"] > 0:
    problems.append(f"shed {r['shed_rate']:.1%} at 100 rps on CPU")
if "banked_at" not in r or "+00:00" not in r["banked_at"]:
    problems.append("missing/naive banked_at provenance stamp")
for p in problems:
    print(f"FAIL: serve-bench result: {p}", file=sys.stderr)
print(f"serve-bench: p50={r['p50_ms']}ms p99={r['value']}ms "
      f"scored={r['scored']} (SLO {r['slo_ms']}ms)")
sys.exit(1 if problems else 0)
EOF
fi

echo "== serve smoke 4/5: sharded fabric serve-bench (8-device mesh) =="
XLA_FLAGS="${XLA_FLAGS:-} --xla_force_host_platform_device_count=8" \
python -m tpu_als.cli serve-bench \
    --users 2000 --items 4096 --rank 32 --k 10 --shortlist-k 64 \
    --qps 200 --duration 3 --slo-ms 2000 --max-wait-ms 2 \
    --mesh-devices 8 --buckets 16,64 \
    --bench-json "$work/BENCH_serve_sharded_smoke.json" \
    >"$work/serve_sharded.out" 2>"$work/serve_sharded.log"
rc=$?
if [ "$rc" -ne 0 ]; then
    echo "FAIL: sharded serve-bench exited $rc" >&2
    tail -5 "$work/serve_sharded.log" >&2
    fail=1
else
    python - "$work/BENCH_serve_sharded_smoke.json" <<'EOF' || fail=1
import json, sys

r = json.load(open(sys.argv[1]))
problems = []
if not r["scored"]:
    problems.append("no request completed (empty latency histograms)")
if not r["slo_met"]:
    problems.append(f"p99 {r['value']}ms blew the loose {r['slo_ms']}ms SLO")
if r.get("backend") != "sharded":
    problems.append(f"resolved backend {r.get('backend')!r}, not sharded")
db = r.get("derived_buckets")
if not db or any(b & (b - 1) for b in db):
    problems.append(f"derived bucket ladder {db!r} missing or not pow2")
if "banked_at" not in r or "+00:00" not in r["banked_at"]:
    problems.append("missing/naive banked_at provenance stamp")
for p in problems:
    print(f"FAIL: sharded serve-bench result: {p}", file=sys.stderr)
print(f"sharded serve-bench: p50={r['p50_ms']}ms p99={r['value']}ms "
      f"scored={r['scored']} backend={r.get('backend')} "
      f"derived_buckets={db}")
sys.exit(1 if problems else 0)
EOF
fi

echo "== serve smoke 5/5: bench regression gate =="
bash scripts/bench_gate.sh || fail=1

if [ "$fail" -ne 0 ]; then
    echo "serve smoke: FAIL" >&2
    exit 1
fi
echo "serve smoke: OK"
