#!/usr/bin/env sh
# omnilint CI gate: exits non-zero on any NEW finding (beyond the
# committed analysis/baseline.json and inline suppressions) across ALL
# rule families OL1-OL13 — the omnirace concurrency rules (OL7-OL9;
# scripts/racecheck.sh runs just those plus the runtime detector), the
# omniflow package-wide rules (OL10 hostile-input taint, OL11
# recompile-hazard), and the omnileak path-sensitive rules (OL12
# resource-lifecycle, OL13 typestate) included — AND on any stale suppression: a
# `# omnilint: disable=OLx` comment that no longer suppresses anything
# (or a baseline entry nothing produces) is dead armor that would
# silently bless the next regression, so the audit is a hard gate.
#
# OMNI_LINT_SARIF=path additionally writes a SARIF 2.1.0 document of
# the new findings for CI annotation (GitHub code scanning, reviewdog).
#
# The tier-1 pytest run exercises the same checks through
# tests/analysis/test_selflint.py; this wrapper is the standalone /
# pre-commit face.  Deliberate contract changes regenerate the baseline:
#
#   python -m vllm_omni_tpu.analysis --update-baseline \
#       vllm_omni_tpu scripts
#
# then commit the baseline.json diff for review like any code change.
set -eu
cd "$(dirname "$0")/.."

if [ -n "${OMNI_LINT_SARIF:-}" ]; then
    set -- --sarif-out "$OMNI_LINT_SARIF" "$@"
fi

# stale-suppression audit rides the SAME analysis pass as the gate
# (--stale-audit) so the package is analyzed once and the audit judges
# exactly the inputs the gate ran with; only meaningful on full-family
# runs, so an explicit --rules invocation skips it (racecheck-style
# subset callers)
case "$*" in
    *--rules*) ;;
    *) set -- --stale-audit "$@" ;;
esac
python -m vllm_omni_tpu.analysis "$@" vllm_omni_tpu scripts
