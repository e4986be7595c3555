#!/usr/bin/env sh
# Unified-ragged-batching gate: the ragged paged-attention kernel vs its
# XLA reference oracle (GQA, empty-seq, 1-token decode rows, multi-query
# spec-verify rows, page/q-block boundary lengths, interpret mode) plus
# the engine-level contract — greedy/spec/logprobs/hidden/embeds/async
# scenarios held to a plain float32 reference at 2e-5, ONE
# device dispatch per mixed step, chunked-prefill resume, preemption
# mid-chunk/mid-verify, async pipelining with the retired fallback
# reasons asserted absent, padding-efficiency vs the old bucket grid —
# and the mixed spec+logprobs+embeds serving smoke (deterministic
# fallback/completion assertions on the PR 7 harness accounting).
#
# Standalone face of the same coverage tier-1 carries (tests/ops and
# tests/engine are fast directories), sitting next to
# scripts/asyncstep.sh, scripts/omnilint.sh and scripts/faultmatrix.sh
# as a pre-merge gate:
#
#   scripts/ragged.sh                # ragged kernel + unified engine
#   scripts/ragged.sh -k dispatch    # pass-through pytest args
set -eu
cd "$(dirname "$0")/.."
# JAX on CPU: the tests compare float32 logprobs and greedy streams on
# the fake-device path; they must never touch a real chip a colocated serving
# process owns
env JAX_PLATFORMS=cpu python -m pytest \
    tests/ops/test_ragged_paged_attention.py \
    tests/ops/test_autotune.py \
    tests/engine/test_unified_batch.py \
    tests/engine/test_served_against_reference.py \
    -q -p no:cacheprovider -m "not slow" "$@"
# mixed serving smoke: spec + logprobs + embeds + sampled tenants on
# one async engine — the retired fallback reasons must stay at zero
# and every offered request must complete
exec env JAX_PLATFORMS=cpu PYTHONPATH=. python scripts/mixed_smoke.py \
    --rates 8 --requests 16 --check-fallback
