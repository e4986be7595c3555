"""Second-backend platform template — every override point, documented.

Role of the reference's out-of-tree platform support (reference:
vllm_omni/platforms/interface.py:20 ``OmniPlatform`` + NPU plugin
platforms resolved through entry points): a new accelerator backend
("NPU-grade" port) subclasses ``OmniPlatform``, overrides the hooks
below, and registers itself — either programmatically
(``platforms.register_platform``) or through the
``vllm_omni_tpu.platforms`` entry-point group — WITHOUT touching
in-tree code.  ``ExamplePlatform`` here is a complete, runnable
instance (it executes on the CPU backend, standing in for a device
whose pallas kernels don't compile), used by the platform-template
tests to prove a third-party backend drives the full engine stack.

Override points and what consumes each:

==========================  =============================================
hook                        consumed by
==========================  =============================================
ar_attention_backend        ops/_dispatch.py — paged-attention impl pick
diffusion_attention_backend ops/_dispatch.py — DiT flash-attention pick
supports_pallas             ops (interpret-mode fallback for kernels)
preferred_dtype             config/model.resolve_dtype ("auto" dtype)
stage_device_env            spawned stage workers' pre-import env
hbm_bytes / memory_stats    platforms/memory.py stage HBM budgeting
peak_tflops_bf16            metrics/roofline.py MFU denominators
initialize                  once-per-process backend bring-up
==========================  =============================================
"""

from __future__ import annotations

from vllm_omni_tpu.platforms.interface import OmniPlatform


class ExamplePlatform(OmniPlatform):
    """A fully-wired example backend (CPU execution underneath).

    A real port changes: the attention backends to its kernel library,
    ``stage_device_env`` to its device-visibility env vars,
    ``peak_tflops_bf16`` to the chip's spec sheet, and ``initialize``
    to its runtime bring-up (plugin registration, topology discovery).
    """

    name = "example"
    supports_pallas = False  # kernels run via the XLA fallbacks

    def initialize(self) -> None:
        """Once-per-process backend bring-up.  A real device plugin
        would initialize its PJRT client / driver here; the example
        needs nothing."""

    def ar_attention_backend(self) -> str:
        return "xla"

    def diffusion_attention_backend(self) -> str:
        return "xla"

    def preferred_dtype(self):
        import jax.numpy as jnp

        return jnp.float32

    def peak_tflops_bf16(self) -> float:
        return 1.0  # spec-sheet number of the ported device

    def stage_device_env(self, devices: str = "all") -> dict:
        # the env a spawned worker needs to bind only its device share
        # (the CUDA_VISIBLE_DEVICES / TPU_VISIBLE_CHIPS analogue)
        return {"JAX_PLATFORMS": "cpu", "OMNI_TPU_PALLAS_INTERPRET": "1"}
