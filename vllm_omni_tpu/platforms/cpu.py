"""CPU platform: unit tests + virtual multi-device meshes
(XLA_FLAGS=--xla_force_host_platform_device_count=N).  Mirrors the
reference's "cpu marker" test strategy (tests/conftest.py:10-11 forcing
VLLM_TARGET_DEVICE=cpu)."""

from __future__ import annotations

from vllm_omni_tpu import envs
from vllm_omni_tpu.platforms.interface import OmniPlatform


class CpuPlatform(OmniPlatform):
    name = "cpu"
    supports_pallas = False  # pallas runs in interpret mode only

    def ar_attention_backend(self) -> str:
        override = envs.OMNI_TPU_AR_ATTENTION_BACKEND
        if override != "auto":
            return override
        return "xla"

    def diffusion_attention_backend(self) -> str:
        override = envs.OMNI_TPU_DIFFUSION_ATTENTION_BACKEND
        if override != "auto":
            return override
        return "xla"

    # peak_tflops_bf16 / peak_hbm_gbps stay at the interface's 0.0: no
    # peak is assumed for "the CPU", and the MFU / MBU gauges read 0

    def stage_device_env(self, devices: str = "all") -> dict:
        # children must not grab a TPU the parent may hold
        return {"JAX_PLATFORMS": "cpu", "OMNI_TPU_PALLAS_INTERPRET": "1"}

    def preferred_dtype(self):
        import jax.numpy as jnp

        return jnp.float32
