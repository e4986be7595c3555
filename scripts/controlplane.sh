#!/usr/bin/env sh
# Control-plane gate: omnictl end to end — the fake-clock controller
# matrix (pressure model, hysteresis/cooldown anti-flap, the
# drain -> quiesce -> flip -> re-admit state machine, autoscale
# warmup/floors/SLO gating, ring bounds), the WFQ scheduler contract
# (DRR hand-oracle, starvation freedom, priority-ordered shed,
# deferral ledger), the router actuator surface (set_role /
# add_replica / remove_replica / refresh_gauges regression), the
# tiny-model e2e matrix (re-role mid-stream bit-identical to the
# colocated oracle, controller-driven re-role on a live fleet with a
# validate-clean /metrics render, seeded replica-kill convergence
# without flapping, the two-tenant WFQ /metrics split, and a
# prefill-pressure burst replayed open-loop against a live service
# whose controller thread must re-role at least once).
#
# Standalone face of the same coverage tier-1 carries
# (tests/controlplane + tests/core/test_wfq.py are fast), sitting next
# to scripts/disagg.sh and scripts/loadgen.sh as a pre-merge gate:
#
#   scripts/controlplane.sh              # the whole control-plane contract
#   scripts/controlplane.sh -k rerole    # pass-through pytest args
set -eu
cd "$(dirname "$0")/.."
# JAX on CPU: the e2e kills replicas and flips roles on purpose; it
# must never touch a real TPU chip a colocated serving process owns
exec env JAX_PLATFORMS=cpu python -m pytest \
    tests/controlplane/ tests/core/test_wfq.py \
    -q -p no:cacheprovider -m "not slow" "$@"
