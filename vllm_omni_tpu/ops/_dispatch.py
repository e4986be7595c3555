"""Backend dispatch for ops: pallas-native (TPU), pallas-interpret (CPU
tests), or pure-XLA fallback.  The analogue of the reference's attention
backend selector (vllm_omni/diffusion/attention/selector.py:54-85) and
CustomOp dispatch base (diffusion/layers/custom_op.py:9)."""

from __future__ import annotations

import collections
import functools

from vllm_omni_tpu.logger import init_logger

logger = init_logger(__name__)

#: (op, reason) -> traces that auto-dispatched to the XLA path although the
#: platform compiles Pallas natively.  Dispatch runs at trace time, so one
#: count is one compiled shape, not one call.
native_fallbacks: collections.Counter = collections.Counter()


@functools.cache
def pallas_mode() -> str:
    """"native" | "interpret" | "off"."""
    from vllm_omni_tpu import envs
    from vllm_omni_tpu.platforms import current_platform

    if envs.OMNI_TPU_PALLAS_INTERPRET:
        return "interpret"
    if current_platform().supports_pallas:
        return "native"
    return "interpret"


def interpret_flag() -> bool:
    return pallas_mode() == "interpret"


def auto_use_pallas(op: str, reason: str = "") -> bool:
    """The ``use_pallas=None`` decision of every op: the kernel on a
    platform that compiles it, the XLA path elsewhere.  ``reason`` names
    why this call's shape cannot take the kernel; on a native platform
    that is counted and logged, never silent — the XLA path of the paged
    kernels is an O(T * max_ctx) gather."""
    if pallas_mode() != "native":
        return False
    if not reason:
        return True
    if not native_fallbacks[op, reason]:
        logger.warning("%s: %s — running the XLA path on a platform that "
                       "compiles the Pallas kernel", op, reason)
    native_fallbacks[op, reason] += 1
    return False
