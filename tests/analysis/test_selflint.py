"""Whole-package self-lint: the repo must be clean against its own
committed baseline — the tier-1 face of the omnilint gate (the same
checks `scripts/omnilint.sh` runs in CI).

If this test fails you either introduced a real OL1-OL11 violation
(fix it or add a reasoned `# omnilint: disable=OLx - why`), or you
deliberately changed a contract (regenerate the baseline with
`python -m vllm_omni_tpu.analysis --update-baseline vllm_omni_tpu
scripts` and commit the diff).

The full run (every family over every file, including the package-wide
OL10/OL11 finalize pass) is computed once per test session and shared
by every assertion here — it is the expensive part.
"""

import os

from vllm_omni_tpu.analysis import (
    analyze_paths,
    apply_baseline,
    load_baseline,
    new_findings,
    stale_suppressions,
)
from vllm_omni_tpu.analysis.engine import REPO_ROOT
from vllm_omni_tpu.analysis.manifest import validate_manifest

LINT_TARGETS = ["vllm_omni_tpu", "scripts"]

_CACHE: dict = {}


def _full_run():
    if not _CACHE:
        state: dict = {}
        paths = [os.path.join(REPO_ROOT, p) for p in LINT_TARGETS]
        _CACHE["findings"] = analyze_paths(paths, run_state=state)
        _CACHE["state"] = state
    return _CACHE["findings"], _CACHE["state"]


def test_manifest_entries_resolve():
    # a renamed module/class must fail here, not silently un-lint
    validate_manifest()


def test_package_is_clean_against_committed_baseline():
    findings, _ = _full_run()
    new = new_findings(apply_baseline(list(findings), load_baseline()))
    assert new == [], "\n".join(f.render() for f in new)


def test_baseline_entries_still_match_real_findings():
    # a baseline fingerprint nothing produces anymore is stale debt that
    # silently widens the gate — force the regeneration commit (same
    # definition the CLI audit gates on)
    from vllm_omni_tpu.analysis.engine import stale_baseline_entries

    findings, _ = _full_run()
    stale = stale_baseline_entries(findings, load_baseline())
    assert stale == [], "\n".join(f"stale baseline entry: {fp}"
                                  for fp in stale)


def test_no_stale_suppressions_in_tree():
    # every `# omnilint: disable` in the tree must still suppress a
    # real finding — dead armor blesses the next regression silently
    _, state = _full_run()
    stale = stale_suppressions(state)
    assert stale == [], "\n".join(
        f"{p}:{ln}: stale suppression disable={r}" for p, ln, r in stale)
