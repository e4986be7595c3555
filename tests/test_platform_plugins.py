"""Platform-layer depth + plugin system + SD3 sibling (VERDICT r1 rows
5/61 — the plugin system and a platform layer things dispatch through)."""

import os
import sys
import textwrap

import numpy as np
import pytest

from vllm_omni_tpu.platforms import (
    current_platform,
    register_platform,
    reset_platform,
)


def test_platform_surface():
    p = current_platform()
    assert p.name in ("cpu", "tpu")
    assert p.device_count() >= 1
    assert isinstance(p.device_kind(), str)
    # no peak is assumed for the CPU; a TPU's comes from the table
    assert p.peak_tflops_bf16() > 0 or p.name == "cpu"
    assert os.path.isdir(p.default_stage_config_dir())
    # every in-tree stage YAML is discoverable through the platform
    yamls = os.listdir(p.default_stage_config_dir())
    assert any(y.endswith(".yaml") for y in yamls)
    env = p.stage_device_env("all")
    assert isinstance(env, dict)


def test_cpu_stage_device_env_scopes_children():
    from vllm_omni_tpu.platforms.cpu import CpuPlatform

    env = CpuPlatform().stage_device_env("all")
    assert env["JAX_PLATFORMS"] == "cpu"


def test_tpu_platform_peak_table():
    from vllm_omni_tpu.platforms.tpu import TpuPlatform

    class FakeV5e(TpuPlatform):
        def device_kind(self):
            return "TPU v5 lite0"

    class FakeV6(TpuPlatform):
        def device_kind(self):
            return "TPU v6e"

    class FakeUnlisted(TpuPlatform):
        def device_kind(self):
            return "TPU v9 hyper"

    assert FakeV5e().peak_tflops_bf16() == 197.0
    assert FakeV6().peak_tflops_bf16() == 918.0
    # an unlisted generation is an error, never the v5e row
    for peak in (FakeUnlisted().peak_tflops_bf16,
                 FakeUnlisted().peak_hbm_gbps):
        with pytest.raises(ValueError, match="v9 hyper"):
            peak()
    # one chip per worker is scoped; a larger subset is refused
    env = FakeV5e().stage_device_env("2")
    assert env["TPU_VISIBLE_CHIPS"] == "2"
    assert env["TPU_PROCESS_PORT"] != FakeV5e().stage_device_env(
        "3")["TPU_PROCESS_PORT"]
    assert FakeV5e().stage_device_env("all") == {}
    with pytest.raises(ValueError, match="multi-chip subset"):
        FakeV5e().stage_device_env("0,1")


def test_cpu_must_be_asked_for():
    """Detection never drifts onto the CPU: a process whose JAX fell back
    to the cpu backend without JAX_PLATFORMS=cpu (libtpu could not take
    the chip) is refused, not served from the XLA references."""
    import jax

    from vllm_omni_tpu import platforms

    assert jax.config.jax_platforms == "cpu"  # tests/conftest.py asked
    assert platforms._detect().name == "cpu"
    try:
        jax.config.update("jax_platforms", None)
        with pytest.raises(RuntimeError, match="JAX_PLATFORMS=cpu"):
            platforms._detect()
    finally:
        jax.config.update("jax_platforms", "cpu")


def test_env_plugin_loading(tmp_path, monkeypatch):
    """OMNI_TPU_PLUGINS modules load and can override platform
    detection (reference: entry-point platform plugins,
    plugins/__init__.py:24-81)."""
    mod = tmp_path / "my_omni_plugin.py"
    mod.write_text(textwrap.dedent("""
        from vllm_omni_tpu.platforms.cpu import CpuPlatform

        class MyPlatform(CpuPlatform):
            name = "my-accelerator"

        CALLED = []

        def register():
            CALLED.append(1)
            import jax
            # claim the active backend so detection picks us
            return jax.default_backend(), MyPlatform
    """))
    monkeypatch.syspath_prepend(str(tmp_path))
    monkeypatch.setenv("OMNI_TPU_PLUGINS", "my_omni_plugin")
    import vllm_omni_tpu.plugins as plugins

    try:
        n = plugins.load_plugins(reload=True)
        assert n >= 1
        import my_omni_plugin

        assert my_omni_plugin.CALLED == [1]
        reset_platform()
        assert current_platform().name == "my-accelerator"
    finally:
        reset_platform()
        # undo the registration so later tests detect normally
        from vllm_omni_tpu import platforms as plat_mod

        plat_mod._registered.clear()
        sys.modules.pop("my_omni_plugin", None)
        reset_platform()


def test_plugin_failure_is_non_fatal(monkeypatch):
    monkeypatch.setenv("OMNI_TPU_PLUGINS", "definitely_not_a_module")
    import vllm_omni_tpu.plugins as plugins

    # must not raise
    plugins.load_plugins(reload=True)


def test_chip_smoke_refuses_to_run_without_a_chip():
    """The chip check fails at its device gate on the CPU: non-zero exit,
    a message that no TPU was found, and no result line."""
    import subprocess

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.run(
        [sys.executable, "chip_smoke.py"], cwd=root, timeout=120,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
        capture_output=True, text=True)
    assert proc.returncode != 0
    assert "no TPU found" in proc.stderr
    assert '"ok"' not in proc.stdout


# ----------------------------------------------------------------- SD3
@pytest.mark.slow  # full SD3 pipeline build; registry coverage lives in test_registry_covers_all_reference_archs
def test_sd3_pipeline_and_registry():
    import jax
    import jax.numpy as jnp

    from vllm_omni_tpu.diffusion.request import (
        OmniDiffusionRequest,
        OmniDiffusionSamplingParams,
    )
    from vllm_omni_tpu.models.registry import DiffusionModelRegistry
    from vllm_omni_tpu.models.sd3.pipeline import (
        SD3Pipeline,
        SD3PipelineConfig,
    )

    assert DiffusionModelRegistry.resolve(
        "StableDiffusion3Pipeline") is SD3Pipeline
    pipe = SD3Pipeline(SD3PipelineConfig.tiny(), dtype=jnp.float32)
    sp = OmniDiffusionSamplingParams(
        height=16, width=16, num_inference_steps=2, guidance_scale=5.0,
        seed=0)
    out = pipe.forward(OmniDiffusionRequest(
        prompt=["a cat"], sampling_params=sp, request_ids=["r"]))
    assert out[0].data.shape == (16, 16, 3)
    # CFG is live: guidance_scale=1 (no CFG) differs
    sp2 = OmniDiffusionSamplingParams(
        height=16, width=16, num_inference_steps=2, guidance_scale=1.0,
        seed=0)
    out2 = pipe.forward(OmniDiffusionRequest(
        prompt=["a cat"], sampling_params=sp2, request_ids=["r2"]))
    assert (out[0].data != out2[0].data).any()
    # deterministic
    out3 = pipe.forward(OmniDiffusionRequest(
        prompt=["a cat"], sampling_params=sp, request_ids=["r3"]))
    np.testing.assert_array_equal(out[0].data, out3[0].data)


def test_sd3_rejects_flux_shape():
    import jax.numpy as jnp
    import pytest

    from vllm_omni_tpu.models.flux.transformer import FluxDiTConfig
    from vllm_omni_tpu.models.sd3.pipeline import (
        SD3Pipeline,
        SD3PipelineConfig,
    )
    import dataclasses

    cfg = SD3PipelineConfig.tiny()
    bad = dataclasses.replace(cfg, dit=FluxDiTConfig.tiny())  # has singles
    with pytest.raises(ValueError, match="double-stream"):
        SD3Pipeline(bad, dtype=jnp.float32)
