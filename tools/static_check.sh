#!/usr/bin/env bash
# Round-8 static-analysis gate: the machine-checked project invariants.
#
#   1. tools/ftpu_lint.py        — AST rules over fabric_tpu/:
#                                  fault-point registry, metric-drift,
#                                  silent-swallow, host-sync-in-hot-path
#                                  (waiver grammar: # ftpu-lint:
#                                  allow-<rule>(<reason>))
#   2. tools/ftpu_check.py       — whole-program call-graph rules
#                                  (docs/static_analysis.md): seam
#                                  reachability proofs for discovered
#                                  device dispatch, retrace-hazard
#                                  detection inside trace regions, and
#                                  the cross-thread lockset race rule
#                                  (waiver grammar: # ftpu-check:
#                                  allow-<rule>(<reason>); reasoned
#                                  baseline in
#                                  tools/ftpu_check_baseline.json)
#   3. gendoc --check            — docs/metrics_reference.md must match
#                                  the declared *Opts literals exactly
#   4. FTPU_LOCKCHECK=1 subset   — the threaded fast subset runs under
#                                  the lock-order sanitizer
#                                  (fabric_tpu/common/lockcheck.py):
#                                  any A→B/B→A inversion or lock held
#                                  across a device dispatch /
#                                  injected-fault stall FAILS the run
#                                  (tests/conftest.py sessionfinish)
#
# Standalone: tools/static_check.sh
# From the chaos gate: tools/chaos_check.sh static
set -euo pipefail
cd "$(dirname "$0")/.."

PYTEST=(env JAX_PLATFORMS=cpu python -m pytest -q -m 'not slow'
        -p no:cacheprovider -p no:randomly)

echo "== static_check 1/4: ftpu_lint"
python tools/ftpu_lint.py

echo "== static_check 2/4: ftpu_check (whole-program)"
python tools/ftpu_check.py

echo "== static_check 3/4: gendoc --check"
python -m fabric_tpu.common.gendoc --check

echo "== static_check 4/4: lock-order sanitizer (threaded subset)"
FTPU_LOCKCHECK=1 "${PYTEST[@]}" \
    tests/test_lockcheck.py tests/test_ftpu_lint.py \
    tests/test_chaos.py tests/test_commit_pipeline.py \
    tests/test_pipeline_overlap.py tests/test_backoff.py \
    tests/test_overload.py tests/test_device_health.py \
    tests/test_tracing.py tests/test_net_chaos.py \
    tests/test_devicecost.py tests/test_cluster_trace.py \
    tests/test_adaptive.py tests/test_bls12_381_device.py

echo "static_check: all gates green"
