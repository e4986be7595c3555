"""TPU-native op library.

Pallas replacements for the native CUDA ops the reference consumes through
its vllm / flash-attn dependencies (SURVEY.md §2.10): RMSNorm, fused
RoPE/MRoPE, dense flash attention (DiT blocks), paged attention + KV-cache
scatter (AR decode), plus jit-safe sampling ops.  Every op has a pure-JAX
reference implementation (`*_ref`) used for numerics tests and as the XLA
fallback on CPU.
"""

from vllm_omni_tpu.ops.rmsnorm import rms_norm, rms_norm_ref
from vllm_omni_tpu.ops.rope import (
    apply_partial_rope,
    apply_rope,
    apply_rope_pairs_ref,
    apply_rope_ref,
    compute_rope_freqs,
    compute_mrope_freqs,
    pairs_to_halves,
)
from vllm_omni_tpu.ops.attention import flash_attention, attention_ref
from vllm_omni_tpu.ops.paged_attention import (
    cache_data,
    cache_is_quantized,
    cache_shape,
    gather_pages,
    paged_attention_ref,
    write_kv_cache,
)
from vllm_omni_tpu.ops.ragged_paged_attention import (
    paged_attention,
    ragged_paged_attention,
    ragged_paged_attention_ref,
)
from vllm_omni_tpu.ops.activation import silu_mul, gelu_tanh_mul

__all__ = [
    "rms_norm",
    "rms_norm_ref",
    "apply_partial_rope",
    "apply_rope",
    "apply_rope_pairs_ref",
    "apply_rope_ref",
    "compute_rope_freqs",
    "compute_mrope_freqs",
    "pairs_to_halves",
    "flash_attention",
    "attention_ref",
    "cache_data",
    "cache_is_quantized",
    "cache_shape",
    "gather_pages",
    "paged_attention",
    "paged_attention_ref",
    "ragged_paged_attention",
    "ragged_paged_attention_ref",
    "write_kv_cache",
    "silu_mul",
    "gelu_tanh_mul",
]
