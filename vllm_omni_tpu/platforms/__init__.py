"""Platform resolution (reference: vllm_omni/platforms/__init__.py:153-165
``current_omni_platform`` lazy singleton).

On the reference, platform detection probes NVML/amdsmi/torch to pick
CUDA/ROCm/XPU/NPU.  Here the platforms are the JAX backends: TPU when JAX
found one, CPU only when the caller asked for it (``JAX_PLATFORMS=cpu``,
as the tests and the CPU-runnable scripts set) — a TPU program that lands
on the CPU because libtpu could not take the chip must say so, not serve
from the XLA references.  Entry-point plugins can still override via
``register_platform``.
"""

from __future__ import annotations

from typing import Optional

from vllm_omni_tpu.platforms.interface import OmniPlatform

_current: Optional[OmniPlatform] = None
_registered: dict[str, type[OmniPlatform]] = {}


def register_platform(name: str, cls: type[OmniPlatform]) -> None:
    _registered[name] = cls


def _detect() -> OmniPlatform:
    import jax

    backend = jax.default_backend()
    if backend in _registered:
        return _registered[backend]()
    if backend == "tpu":
        from vllm_omni_tpu.platforms.tpu import TpuPlatform

        return TpuPlatform()
    asked = (jax.config.jax_platforms or "").split(",")[0]
    if backend == "cpu" and asked == "cpu":
        from vllm_omni_tpu.platforms.cpu import CpuPlatform

        return CpuPlatform()
    raise RuntimeError(
        f"JAX's default backend is {backend!r} but JAX_PLATFORMS is "
        f"{jax.config.jax_platforms!r}: no TPU was found (or libtpu could "
        "not take the chip — one process per chip) and the CPU was not "
        "asked for.  Set JAX_PLATFORMS=cpu to run on the CPU on purpose, "
        "or register a platform plugin for this backend.")


def current_platform() -> OmniPlatform:
    global _current
    if _current is None:
        # plugins first: a platform plugin registered for the active jax
        # backend must win detection (reference: entry-point override,
        # platforms/__init__.py:118-151)
        from vllm_omni_tpu.plugins import load_plugins

        load_plugins()
        _current = _detect()
        # once-per-process backend bring-up (PJRT plugin registration
        # etc. for out-of-tree platforms — template.py)
        _current.initialize()
    return _current


def reset_platform() -> None:
    """Testing hook."""
    global _current
    _current = None


def default_stage_device_env(devices: str = "all") -> dict:
    """Child-process device scoping WITHOUT initializing jax in the
    caller: the orchestrator parent of an all-process pipeline must never
    touch the TPU runtime itself (acquiring the chips its children need),
    so this sniffs environment variables only.  The per-platform
    ``stage_device_env`` methods remain for callers that already hold a
    platform."""
    import os

    if os.environ.get("JAX_PLATFORMS", "").startswith("cpu"):
        from vllm_omni_tpu.platforms.cpu import CpuPlatform

        return CpuPlatform().stage_device_env(devices)
    if devices in ("", "all"):
        return {}
    from vllm_omni_tpu.platforms.tpu import TpuPlatform

    return TpuPlatform().stage_device_env(devices)
