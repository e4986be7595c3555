import jax
import jax.numpy as jnp
import numpy as np
import pytest

from vllm_omni_tpu.ops import (
    attention_ref,
    paged_attention,
    paged_attention_ref,
    write_kv_cache,
)
from vllm_omni_tpu.ops.paged_attention import init_kv_cache


def test_write_then_read_roundtrip(rng):
    hkv, pages, ps, d = 2, 8, 4, 64
    (kc, vc), = init_kv_cache(1, pages, ps, hkv, d, jnp.float32)
    t = 10
    k_new = jax.random.normal(rng, (t, hkv, d), jnp.float32)
    v_new = jax.random.normal(jax.random.PRNGKey(1), (t, hkv, d), jnp.float32)
    # tokens go to pages 2 and 5 (slots 8..11 and 20..25)
    slots = jnp.array([8, 9, 10, 11, 20, 21, 22, 23, 24, 25], jnp.int32)
    kc, vc = write_kv_cache(kc, vc, k_new, v_new, slots)
    flat = np.asarray(kc.reshape(hkv, pages * ps, d))
    np.testing.assert_allclose(
        flat[:, np.asarray(slots)], np.asarray(jnp.moveaxis(k_new, 1, 0))
    )
    # negative slot (padding) is dropped
    kc2, _ = write_kv_cache(kc, vc, k_new[:1] * 7, v_new[:1], jnp.array([-1]))
    np.testing.assert_allclose(np.asarray(kc2), np.asarray(kc))


def _build_cache_from_dense(k_dense, v_dense, page_size, block_tables, ctx_lens):
    """Scatter dense per-seq KV [B, L, Hkv, D] into a paged cache."""
    b, L, hkv, d = k_dense.shape
    num_pages = int(block_tables.max()) + 2
    (kc, vc), = init_kv_cache(1, num_pages, page_size, hkv, d, jnp.float32)
    for i in range(b):
        n = int(ctx_lens[i])
        pages_needed = (n + page_size - 1) // page_size
        slots = []
        for p in range(pages_needed):
            base = int(block_tables[i, p]) * page_size
            for o in range(page_size):
                if p * page_size + o < n:
                    slots.append(base + o)
        slots = jnp.asarray(slots, jnp.int32)
        kc, vc = write_kv_cache(kc, vc, k_dense[i, :n], v_dense[i, :n], slots)
    return kc, vc


@pytest.mark.parametrize("use_pallas", [False, True])
def test_paged_decode_matches_dense(rng, use_pallas):
    b, h, hkv, d, page = 3, 4, 2, 64, 4
    ctx_lens = np.array([9, 4, 14])
    L = 16
    k1, k2, k3 = jax.random.split(rng, 3)
    q = jax.random.normal(k1, (b, h, d), jnp.float32)
    k_dense = jax.random.normal(k2, (b, L, hkv, d), jnp.float32)
    v_dense = jax.random.normal(k3, (b, L, hkv, d), jnp.float32)
    # non-trivial page assignment
    block_tables = np.array(
        [[3, 1, 6, 0], [2, 0, 0, 0], [7, 4, 5, 8]], np.int32
    )
    kc, vc = _build_cache_from_dense(
        k_dense, v_dense, page, block_tables, ctx_lens
    )
    got = paged_attention(
        q, kc, vc, jnp.asarray(block_tables), jnp.asarray(ctx_lens),
        use_pallas=use_pallas,
    )
    # oracle: dense attention per sequence over its valid prefix
    for i in range(b):
        n = int(ctx_lens[i])
        want = attention_ref(
            q[i][None, None],  # [1, 1, H, D]
            k_dense[i, :n][None],
            v_dense[i, :n][None],
        )[0, 0]
        np.testing.assert_allclose(
            np.asarray(got[i]), np.asarray(want), atol=2e-3, rtol=1e-3,
            err_msg=f"seq {i} (use_pallas={use_pallas})",
        )


def test_paged_decode_empty_context(rng):
    b, h, hkv, d, page = 1, 2, 2, 64, 4
    (kc, vc), = init_kv_cache(1, 4, page, hkv, d, jnp.float32)
    q = jax.random.normal(rng, (b, h, d), jnp.float32)
    out = paged_attention(
        q, kc, vc, jnp.zeros((1, 2), jnp.int32), jnp.array([0]),
        use_pallas=True,
    )
    np.testing.assert_allclose(np.asarray(out), 0.0)


def test_auto_dispatch_counts_native_fallbacks(monkeypatch):
    """On a platform that compiles the kernels, a shape that misses a
    tiling gate is counted (and takes the XLA path); on the interpreted
    CPU lane nothing is counted."""
    from vllm_omni_tpu.ops import _dispatch
    from vllm_omni_tpu.ops.paged_attention import paged_tiling_reason

    assert paged_tiling_reason(128, 16) == ""
    reason = paged_tiling_reason(16, 4)
    assert "head_dim 16" in reason
    monkeypatch.setattr(_dispatch, "native_fallbacks",
                        type(_dispatch.native_fallbacks)())
    assert not _dispatch.auto_use_pallas("paged_attention", reason)
    assert not _dispatch.native_fallbacks
    monkeypatch.setattr(_dispatch, "pallas_mode", lambda: "native")
    assert _dispatch.auto_use_pallas("paged_attention")
    assert not _dispatch.auto_use_pallas("paged_attention", reason)
    assert not _dispatch.auto_use_pallas("paged_attention", reason)
    assert _dispatch.native_fallbacks["paged_attention", reason] == 2
