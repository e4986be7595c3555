#!/usr/bin/env sh
# omniscope + omniaffinity gate: the fleet cache-economics layer end
# to end — the radix digest's fingerprint consistency through insert /
# evict / tier-demotion / park-restore cycles with the node cap
# enforced, the CacheEconomics board's duplicate-prefix accounting
# against a hand-oracled 3-replica fixture, torn-read immunity on
# /debug/kv and /debug/cache under a mutating writer thread, the
# prefix_hit_rate_low fake-clock alert lifecycle, the shared-prefix
# workload's determinism, and that workload replayed over live
# cache-blind AND prefix-affinity 2 prefill x 2 decode in-proc fleets
# (/metrics clean with every cache series live, bounded digests).
#
# Standalone face of the same coverage tier-1 carries (tests/cache is
# a fast directory), sitting next to scripts/alerts.sh,
# scripts/disagg.sh and scripts/omnilint.sh as a pre-merge gate:
#
#   scripts/cache_econ.sh               # the whole omniscope contract
#   scripts/cache_econ.sh -k digest     # pass-through pytest args
set -eu
cd "$(dirname "$0")/.."
# JAX on CPU: the engines are tiny random-weight models; the gate
# must never touch a real chip a colocated serving process owns
exec env JAX_PLATFORMS=cpu python -m pytest \
    tests/cache/ \
    -q -p no:cacheprovider -m "not slow" "$@"
