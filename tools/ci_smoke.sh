#!/usr/bin/env bash
# CI smoke: the gate that keeps a syntax error (or any import-breaking
# change) out of a seed.  A CPU gate: every step runs with
# JAX_PLATFORMS=cpu (several start worker processes, and a chip belongs
# to one process at a time); the on-chip proof is `python chip_smoke.py`
# through the chip tool.  Escalating checks; fails fast:
#
#   1. byte-compile every module           (catches SyntaxError anywhere)
#   2. import the package                  (catches import-time errors)
#   3. pytest collection of the full suite (catches collection errors in
#      tests -- the failure mode that hid the window.py f-string bug)
#   4. observability smoke: one tiny query with tracing + metrics on,
#      then schema-check the emitted Chrome trace JSON and Prometheus
#      text (tools/check_obs_output.py)
#   5. device-decode scan smoke (CPU backend): a multi-row-group
#      parquet scan through the overlapped upload tunnel, checked
#      against the host-decode oracle, with the assemble/upload metric
#      split validated in the Prometheus dump
#   6. flight-recorder smoke: a 2-worker cluster query with an injected
#      worker crash (spark.rapids.tpu.test.injectFaults) and tracing
#      DISABLED must leave exactly one valid incident bundle, which is
#      schema-checked and triage-rendered
#   7. shuffle-durability smoke: a corrupted committed map output must
#      trigger exactly one lineage rerun and still produce oracle rows
#   8. static analysis: tpu-lint over the package (zero unallowlisted
#      violations, JSON summary printed), SUPPORTED_OPS.md drift check,
#      and a plan-verifier smoke (all 14 NDS corpus plans verify clean;
#      one seeded-broken plan must be rejected with a named reason)
#   9. widened-envelope scan smoke: a mixed-encoding parquet file
#      (PLAIN strings + DATA_PAGE_V2 + DELTA_BINARY_PACKED +
#      DELTA_LENGTH_BYTE_ARRAY) must decode entirely on device —
#      zero host-fallback chunks — and match the host oracle
#  10. SQL frontend smoke: the full NDS SQL corpus parses, compiles
#      and plan-verifies clean (zero parse failures, zero unexpected
#      fallbacks), one SQL query runs end to end on the process
#      cluster against the pandas oracle, and a broken statement
#      leaves a sql_parse_error event-log line
#  11. operator-metrics smoke: EXPLAIN ANALYZE q3 from SQL on a
#      2-worker process cluster yields nonzero cross-worker rows at
#      every scan/join/agg node, persists a schema-valid query-profile
#      JSON, and `profiling compare` renders across two runs
#  12. tpu-lint 2.0 + lock-order watchdog: the dataflow analyses
#      (lock-order/deadlock, ledger resource leaks, jit host-sync
#      taint) must report ZERO findings beyond the checked-in baseline
#      (tools/tpu_lint_baseline.json, schema-validated via
#      check_obs_output.py --lint-report), and the concurrency-heavy
#      test files run with the runtime lock-order watchdog installed
#      must record ZERO inversions of the declared lock hierarchy
#  13. query-lifecycle smoke: a deadline-exceeded query under chaos
#      hang_query must yield exactly one classified query_cancelled
#      event + one incident bundle, and a post-cancel query must run
#      green on the same cluster (no poisoned state)
#  14. spill-durability smoke: a reduce-side out-of-core sort whose
#      disk-spill writes ALL hit injected ENOSPC (chaos disk_full)
#      must run green with classified disk_pressure evidence (event
#      log + exactly one incident bundle), the boot-time orphan sweep
#      must reclaim a planted dead-incarnation spill namespace, and
#      no live namespace may leak a spill file; the spill unit matrix
#      (torn/corrupt/missing/eio/ENOSPC, tests/test_memory.py) runs
#      under the step-12 lock-order watchdog
#
#  15. whole-stage-fusion smoke: q6-shaped scan->filter->project->
#      partial-agg from a multi-row-group parquet file must run ONE
#      spliced XLA program per coalesced batch (fusedDispatches ==
#      scanPrograms, counter-verified), match the host oracle, hit
#      zero fallback chunks, and be bit-exact vs stageFusion off
#
#  16. multi-host mesh smoke: a 2-process jax.distributed mesh over
#      the worker fleet runs one gang join+agg whose shuffle
#      exchanges cross the process boundary, gated on STRUCTURAL
#      counters (process count, cross-process collective epochs,
#      bytes exchanged, device_kind recorded) — never wall-clock —
#      with the stitched driver trace schema-validated
#
#  17. telemetry-warehouse smoke: three queries on a 2-worker cluster
#      (a green agg, a chaos hang_query stall user-cancelled while
#      /status is read mid-flight, a spill_corrupt'd sort completing
#      through a classified retry) must leave EXACTLY three sealed
#      warehouse rows with the right outcome classes, and the drift
#      sentinel must stay silent across a repeat run
#
# Pass --full to also run the tier-1 suite (see ROADMAP.md), bounded to
# 870s like the driver's own gate — with the lock-order watchdog
# enabled, so the whole suite doubles as a hierarchy witness.
set -euo pipefail
cd "$(dirname "$0")/.."

echo "== 1/17 compileall =="
python -m compileall -q spark_rapids_tpu tests

echo "== 2/17 package import =="
JAX_PLATFORMS=cpu python -c "import spark_rapids_tpu; print('import ok:', spark_rapids_tpu.__name__)"

echo "== 3/17 pytest collection =="
JAX_PLATFORMS=cpu python -m pytest tests/ -q --collect-only -m 'not slow' \
    -p no:cacheprovider 2>&1 | tail -3

echo "== 4/17 observability smoke =="
OBS_TMP="$(mktemp -d)"
trap 'rm -rf "$OBS_TMP"' EXIT
JAX_PLATFORMS=cpu python tools/check_obs_output.py --smoke "$OBS_TMP"

echo "== 5/17 device-decode scan smoke =="
JAX_PLATFORMS=cpu python tools/check_obs_output.py --scan-smoke "$OBS_TMP/scan"

echo "== 6/17 flight-recorder smoke =="
JAX_PLATFORMS=cpu python tools/check_obs_output.py --flight-smoke "$OBS_TMP/flight"

echo "== 7/17 shuffle-durability smoke =="
JAX_PLATFORMS=cpu python tools/check_obs_output.py --shuffle-smoke "$OBS_TMP/shuffle"

echo "== 8/17 static analysis (tpu-lint + plan verifier) =="
JAX_PLATFORMS=cpu python tools/tpu_lint.py --json --baseline tools/tpu_lint_baseline.json > "$OBS_TMP/lint-step8.json"
tail -8 "$OBS_TMP/lint-step8.json"
JAX_PLATFORMS=cpu python tools/tpu_lint.py --check-docs
JAX_PLATFORMS=cpu python -m spark_rapids_tpu.analysis.plan_verifier --smoke

echo "== 9/17 widened-envelope scan smoke (mixed encodings) =="
JAX_PLATFORMS=cpu python tools/check_obs_output.py --scan-smoke "$OBS_TMP/scan-envelope" --mixed-encodings

echo "== 10/17 SQL frontend smoke (full corpus + cluster run) =="
JAX_PLATFORMS=cpu python tools/check_obs_output.py --sql-smoke "$OBS_TMP/sql"

echo "== 11/17 operator-metrics smoke (EXPLAIN ANALYZE + profile) =="
JAX_PLATFORMS=cpu python tools/check_obs_output.py --analyze-smoke "$OBS_TMP/analyze"

echo "== 12/17 tpu-lint 2.0 report gate + lock-order watchdog =="
JAX_PLATFORMS=cpu python tools/tpu_lint.py --json --baseline tools/tpu_lint_baseline.json > "$OBS_TMP/lint.json"
JAX_PLATFORMS=cpu python tools/check_obs_output.py --lint-report "$OBS_TMP/lint.json"
RAPIDS_TPU_LOCKWATCH=1 RAPIDS_TPU_LOCKWATCH_OUT="$OBS_TMP/lockwatch.json" \
    JAX_PLATFORMS=cpu python -m pytest tests/test_memory.py \
    tests/test_scan_pipeline.py tests/test_shuffle.py \
    tests/test_scheduler_unit.py tests/test_lifecycle.py \
    -q -m 'not slow' -p no:cacheprovider
JAX_PLATFORMS=cpu python tools/check_obs_output.py --lockwatch "$OBS_TMP/lockwatch.json"

echo "== 13/17 query-lifecycle smoke (deadline cancel under hang_query) =="
JAX_PLATFORMS=cpu python tools/check_obs_output.py --lifecycle-smoke "$OBS_TMP/lifecycle"

echo "== 14/17 spill-durability smoke (out-of-core sort under disk_full) =="
JAX_PLATFORMS=cpu python tools/check_obs_output.py --spill-smoke "$OBS_TMP/spill"

echo "== 15/17 whole-stage-fusion smoke (one program per coalesced batch) =="
JAX_PLATFORMS=cpu python tools/check_obs_output.py --fusion-smoke "$OBS_TMP/fusion"

echo "== 16/17 multi-host mesh smoke (cross-process gang collective) =="
JAX_PLATFORMS=cpu python tools/check_obs_output.py --mesh-smoke "$OBS_TMP/mesh"

echo "== 17/17 telemetry-warehouse smoke (3 outcomes + drift sentinel) =="
JAX_PLATFORMS=cpu python tools/check_obs_output.py --warehouse-smoke "$OBS_TMP/warehouse"

if [[ "${1:-}" == "--full" ]]; then
    echo "== tier-1 (full, watchdog-enabled) =="
    LW_OUT="$OBS_TMP/lockwatch-tier1.json"
    timeout -k 10 870 env JAX_PLATFORMS=cpu RAPIDS_TPU_LOCKWATCH=1 \
        RAPIDS_TPU_LOCKWATCH_OUT="$LW_OUT" python -m pytest tests/ -q \
        -m 'not slow' --continue-on-collection-errors -p no:cacheprovider
    JAX_PLATFORMS=cpu python tools/check_obs_output.py --lockwatch "$LW_OUT"
fi

echo "smoke OK"
