#!/usr/bin/env sh
# Async pipelined-step gate: the sync-vs-async greedy token-equality
# oracle, stop/EOS one-step-lag rollback, preemption/deadline/abort with
# a step in flight, pipelined spec/logprobs/collect_hidden/embeds
# batches (the retired fallback reasons asserted absent), and the
# CPU-backend overlap microbench (overlap ratio > 0).
#
# Standalone face of the same coverage tier-1 carries — tests/engine is
# a fast directory, so tests/engine/test_async_step.py rides
# `pytest -m 'not slow'` exactly like the tests/resilience fast units —
# sitting next to scripts/omnilint.sh and scripts/faultmatrix.sh as a
# pre-merge gate:
#
#   scripts/asyncstep.sh                 # async pipeline suite
#   scripts/asyncstep.sh -k oracle       # pass-through pytest args
set -eu
cd "$(dirname "$0")/.."
# JAX on CPU: the oracle compares bit-identical greedy streams on the
# fake-device path; it must never touch a real chip a colocated serving
# process owns
exec env JAX_PLATFORMS=cpu python -m pytest \
    tests/engine/test_async_step.py \
    -q -p no:cacheprovider -m "not slow" "$@"
