#!/usr/bin/env sh
# omnirace standalone gate: the concurrency-correctness subset.
#
#  1. self-lint with ONLY the OL7-OL9 families enforced (lock
#     discipline against the LOCK_GUARDS manifest, lock-order cycles,
#     blocking-under-lock) — no baseline: concurrency findings are
#     never allowed to accumulate as debt;
#  2. the runtime detector's unit suite plus the connector regression,
#     with OMNI_TPU_LOCK_CHECK=1 so every traced lock records into the
#     live order graph and the seeded-deadlock regression is exercised.
#
# The full tier-1 run covers both anyway (tests/analysis/test_selflint
# and the threaded suites' conftests); this wrapper is the fast
# pre-commit face for concurrency-touching changes.
set -eu
cd "$(dirname "$0")/.."

echo "== omnirace: static (OL7-OL9 self-lint) =="
python -m vllm_omni_tpu.analysis --no-baseline --rules OL7,OL8,OL9 \
    vllm_omni_tpu scripts

echo "== omnirace: runtime (lock-order detector) =="
exec env JAX_PLATFORMS=cpu OMNI_TPU_LOCK_CHECK=1 python -m pytest -q \
    -p no:cacheprovider -p no:xdist -p no:randomly \
    tests/analysis/test_runtime_lockcheck.py \
    tests/analysis/test_rules_lock_discipline.py \
    tests/analysis/test_rules_lock_order.py \
    tests/analysis/test_rules_blocking.py \
    tests/distributed/test_connectors.py
