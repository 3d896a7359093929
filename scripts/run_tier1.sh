#!/usr/bin/env bash
# Tier-1 gate: run the test suite with the Pallas interpret tier forced
# (every compose/norm call exercises the fused kernels through the Pallas
# interpreter on CPU) and fail on any regression below the recorded pass
# count.
#
# Usage:  scripts/run_tier1.sh [extra pytest args...]
# Env:    REPRO_TIER1_MIN_PASS     recorded floor (default below)
#         REPRO_TIER1_MAX_FAIL     allowed failures (default 0)
#         REPRO_TIER1_INSTALL_DEV  "1": pip-install requirements-dev.txt
#                                  first (CI does this; containers without
#                                  network keep the gated skips instead)
#         REPRO_FORCE_TIER         tier to force (default: interpret;
#                                  "default" = leave the dispatch
#                                  unforced, the CI matrix's other leg)
#
# The "N skipped" column is the two hypothesis-gated modules
# (tests/test_property.py, tests/test_ssm_scan.py): with
# requirements-dev.txt installed (CI always does) they RUN and the
# expected skip count is 0; without it they self-skip. The pass floor
# below is the hypothesis-absent count — CI's dev-installed runs pass
# MORE, never fewer.
#
# Baselines (keep in sync with ROADMAP.md; "2 skipped" rows were
# measured on hypothesis-absent containers, see above):
#   seed     127 passed / 81 failed / 2 collection errors
#   post-PR1 250 passed / 0 failed / 2 skipped — every seed failure was
#            JAX API drift, absorbed by src/repro/compat/
#   post-PR2 292 passed / 0 failed / 2 skipped
#   post-PR3 317 passed / 0 failed / 2 skipped (SPMD compose + CI gates)
#   post-PR4 358 passed / 0 failed / 2 skipped (multi-tenant serving + docs)
#   post-PR5 385 passed / 0 failed / 2 skipped (continuous-batching engine)
#   post-PR6 393 passed / 0 failed / 2 skipped (speculative decoding +
#            submit-time adapter pinning)
#   post-PR7 422 passed / 0 failed / 2 skipped (fault-tolerant serving:
#            deadlines, preemption, quarantine, FaultPlan injection)
#   post-PR8 428 passed / 0 failed / 2 skipped (paged KV cache + chunked
#            prefill: block pool, paged==rect bitwise, check_paged gate)
#   post-PR9 443 passed / 0 failed / 2 skipped (fleet serving: traced
#            dynamic grouping, tiered adapter cache, churn fuzzer)
#   post-PR10 474 passed / 0 failed / 2 skipped (observability: lifecycle
#            tracing, latency histograms, metrics export; tracing
#            on == off bitwise)
#   chip bring-up: 504 passed / 0 failed / 0 skipped on JAX 0.9.0 (the
#            six JAX-0.9 failures repaired, kernel compiles for a
#            described v5e, compile-cache helper)
set -euo pipefail
cd "$(dirname "$0")/.."

MIN_PASS="${REPRO_TIER1_MIN_PASS:-504}"
MAX_FAIL="${REPRO_TIER1_MAX_FAIL:-0}"
if [ "${REPRO_TIER1_INSTALL_DEV:-0}" = "1" ]; then
    pip install -q -r requirements-dev.txt
fi
export JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}"
TIER="${REPRO_FORCE_TIER:-interpret}"
if [ "${TIER}" = "default" ]; then
    # CI matrix leg: run with the dispatch left alone (mode=auto resolves
    # to the eager tier on CPU hosts).
    unset REPRO_FORCE_TIER
    TIER="(unforced)"
else
    export REPRO_FORCE_TIER="${TIER}"
fi

out="$(mktemp)"
trap 'rm -f "$out"' EXIT

# || true: pytest exits nonzero on any failure; the gate below decides.
python -m pytest -q "$@" 2>&1 | tee "$out" || true

summary="$(grep -E '[0-9]+ (passed|failed|error)' "$out" | tail -1)"
passed="$(grep -oE '[0-9]+ passed' "$out" | tail -1 | grep -oE '[0-9]+' || echo 0)"
failed="$(grep -oE '[0-9]+ failed' "$out" | tail -1 | grep -oE '[0-9]+' || echo 0)"
errors="$(grep -oE '[0-9]+ errors?' "$out" | tail -1 | grep -oE '[0-9]+' || echo 0)"
skipped="$(grep -oE '[0-9]+ skipped' "$out" | tail -1 | grep -oE '[0-9]+' || echo 0)"
# The only sanctioned skips are the two hypothesis-gated modules, and
# only when hypothesis is absent: with it installed, 0 skips expected —
# a new unexplained skip is a silently-disabled test, which is a FAIL.
if python -c "import hypothesis" >/dev/null 2>&1; then
    EXPECT_SKIP=0
else
    EXPECT_SKIP=2
fi

echo
echo "tier-1 summary: ${summary:-<no pytest summary found>}"
if [ "${errors}" -gt 0 ]; then
    echo "tier-1 FAIL: ${errors} collection error(s) (seed had 2; must stay 0)"
    exit 1
fi
if [ "${failed}" -gt "${MAX_FAIL}" ]; then
    echo "tier-1 FAIL: ${failed} failed > allowed ${MAX_FAIL}"
    exit 1
fi
if [ "${passed}" -lt "${MIN_PASS}" ]; then
    echo "tier-1 FAIL: ${passed} passed < recorded floor ${MIN_PASS}"
    exit 1
fi
if [ $# -eq 0 ] && [ "${skipped}" -ne "${EXPECT_SKIP}" ]; then
    echo "tier-1 FAIL: ${skipped} skipped != expected ${EXPECT_SKIP}" \
         "(hypothesis $(python -c 'import hypothesis' >/dev/null 2>&1 \
          && echo present || echo absent))"
    exit 1
fi
echo "tier-1 OK: ${passed} passed, ${failed} failed, ${skipped} skipped (floor ${MIN_PASS}, tier ${TIER})"

# End-to-end smokes (still under the forced tier, so the fused kernels and
# the frozen-adapter cache path are exercised through the Pallas
# interpreter on every gate). set -e aborts the gate on any failure.
export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"
echo
echo "serve smoke (tier ${TIER}): adapter cache + padded prefill"
python -m repro.launch.serve --arch qwen2-7b --smoke --batch 2 \
    --prompt-len 16 --gen-len 4
echo
echo "multi-tenant serve smoke (tier ${TIER}): LRU cache + grouped decode"
python -m repro.launch.serve --arch qwen2-7b --smoke --batch 2 \
    --prompt-len 16 --gen-len 4 --tenants 3
echo
echo "continuous serve smoke (tier ${TIER}): slot-scheduled engine"
python -m repro.launch.serve --arch qwen2-7b --smoke --batch 2 \
    --prompt-len 16 --gen-len 4 --continuous
echo
echo "speculative serve smoke (tier ${TIER}): draft/verify/rewind + oracle"
python -m repro.launch.serve --arch qwen2-7b --smoke --batch 2 \
    --prompt-len 16 --gen-len 4 --continuous --speculative 3
echo
echo "fault-injection serve smoke (tier ${TIER}): quarantine + deadlines"
echo "  + obs: --trace-out/--metrics-out on the faulty run, then assert"
obs_trace="$(mktemp --suffix=.jsonl)"
obs_prom="$(mktemp --suffix=.prom)"
python -m repro.launch.serve --arch qwen2-7b --smoke --batch 2 \
    --prompt-len 16 --gen-len 4 --continuous --inject nan@3 --deadline 8 \
    --trace-out "$obs_trace" --metrics-out "$obs_prom"
# The poisoned request's lifecycle must end quarantined ->
# terminal(error_numeric), and the metrics snapshot must parse as
# Prometheus text with the quarantine counter visible.
python - "$obs_trace" "$obs_prom" <<'PY'
import json, sys
from repro.obs import parse_prometheus
by_rid = {}
with open(sys.argv[1]) as f:
    for line in f:
        e = json.loads(line)
        if e.get("request_id") is not None:
            by_rid.setdefault(e["request_id"], []).append(e)
poisoned = [rid for rid, evs in by_rid.items()
            if any(e["name"] == "quarantined" for e in evs)]
assert poisoned, "nan@3 left no quarantined request in the trace"
for rid in poisoned:
    names = [e["name"] for e in by_rid[rid]]
    assert names[-2:] == ["quarantined", "terminal"], \
        f"rid {rid}: lifecycle tail {names[-2:]} != quarantined->terminal"
    term = by_rid[rid][-1]
    assert term["data"]["reason"] == "error_numeric", term
parsed = parse_prometheus(open(sys.argv[2]).read())
assert parsed["repro_engine_quarantined_total"] >= 1, \
    "quarantine counter missing from the Prometheus snapshot"
assert any(k.startswith('repro_requests_finished_total{reason="error_numeric"')
           for k in parsed), sorted(parsed)[:5]
print(f"obs smoke OK: {len(poisoned)} poisoned request(s) traced "
      f"quarantined -> terminal(error_numeric); metrics parse as "
      f"Prometheus ({len(parsed)} series, quarantine visible)")
PY
rm -f "$obs_trace" "$obs_prom"
echo
echo "paged serve smoke (tier ${TIER}): block pool + chunked prefill + oracle"
python -m repro.launch.serve --arch qwen2-7b --smoke --batch 2 \
    --prompt-len 16 --gen-len 4 --continuous --paged
echo
echo "fleet serve smoke (tier ${TIER}): dynamic grouping, ONE decode executable"
python -m repro.launch.serve --arch qwen2-7b --smoke --batch 2 \
    --prompt-len 8 --gen-len 4 --rank 4 --fleet 5
echo
echo "bench smoke: compose kernels (incl. matmul-fused) + serving cache"
python -m benchmarks.compose_bench --smoke
python -m benchmarks.serve_bench --smoke
echo
echo "bench-drift gate: analytic bytes models vs committed BENCH_*.json"
python scripts/check_bench_drift.py
echo
echo "docs gate: executable guides + module references (docs/*.md)"
python scripts/check_docs.py
echo "tier-1 smokes OK"
