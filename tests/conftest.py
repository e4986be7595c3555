"""Test harness: force JAX onto CPU with a virtual 8-device mesh so all
parallelism (tp/sp/cfg/dp) is exercised without TPU hardware — the TPU-native
upgrade of the reference's fake-process-group trick
(tests/diffusion/distributed/test_parallel_state_sp_groups.py:20-56), which
could only test group *construction*; a virtual CPU mesh tests collective
*numerics* too.
"""

import os

# Unit tests always run on the virtual CPU mesh, whatever the surrounding
# environment holds: the platform is pinned before JAX is imported.
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ.setdefault("OMNI_TPU_PALLAS_INTERPRET", "1")

import jax  # noqa: E402
import pytest  # noqa: E402


# Test tiers (reference: per-suite stratification,
# vllm_omni pyproject.toml:149-176 / .buildkite/pipeline.yml): heavy
# parity/e2e/multiproc suites are marked ``slow`` by DIRECTORY so
# ``-m "not slow"`` yields a fast core signal (ops/engine/core/
# parallel/sample/config stay in it).  Individual tests can still
# override with their own marks.
_SLOW_DIRS = ("model_loader", "models", "entrypoints", "distributed",
              "diffusion", "metrics")


def pytest_collection_modifyitems(config, items):
    for item in items:
        parts = item.path.parts if hasattr(item, "path") else ()
        # only components BELOW tests/ count — a repo checked out under
        # e.g. /data/models/ must not mark everything slow
        if "tests" in parts:
            parts = parts[parts.index("tests") + 1:]
        if any(d in parts for d in _SLOW_DIRS):
            item.add_marker(pytest.mark.slow)


@pytest.fixture(scope="session")
def devices8():
    devs = jax.devices()
    assert len(devs) >= 8, f"expected 8 virtual devices, got {len(devs)}"
    return devs[:8]


@pytest.fixture()
def rng():
    return jax.random.PRNGKey(0)
