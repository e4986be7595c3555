"""int8-resident paged KV: quantized write op, in-kernel dequant, and
the shared absmax quantizer module (kvcache/quant.py).

Oracle strategy: the kernels on a QUANTIZED cache must match the XLA
reference on the SAME quantized cache tightly (both dequantize with the
identical per-(head, page) scales), and the reference on the quantized
cache must match the full-precision oracle within a PER-HEAD bound
derived from the scales actually in the cache — attention output is a
convex combination of V rows, so the value-side error is bounded by
half a quantization step of the largest V scale a head saw, and the
key-side error perturbs softmax weights by at most a factor bounded by
the score perturbation (documented in docs/performance.md)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from vllm_omni_tpu.kvcache.quant import (
    bytes_per_token,
    concat_payloads,
    dequantize_payload,
    is_quant_payload,
    page_bytes,
    pages_for_budget,
    payload_seq_len,
    quantize_payload,
    trim_payload,
)
from vllm_omni_tpu.ops import (
    cache_is_quantized,
    gather_pages,
    paged_attention,
    paged_attention_ref,
    ragged_paged_attention,
    ragged_paged_attention_ref,
    write_kv_cache,
)
from vllm_omni_tpu.ops.autotune import auto_ragged_blocks
from vllm_omni_tpu.ops.paged_attention import init_kv_cache
from vllm_omni_tpu.ops.ragged_paged_attention import align_to_block

TB = 8


def _write_tokens(cache, x, slots):
    """Write [T, Hkv, D] rows at flat slots into ONE cache half pair."""
    (kc, vc), = cache
    kc, vc = write_kv_cache(kc, vc, jnp.asarray(x[0]), jnp.asarray(x[1]),
                            jnp.asarray(slots))
    return [(kc, vc)]


def _build_pair(specs, hkv, d, page, s_max, max_pages, seed=0):
    """Write the SAME random tokens into a dense f32 cache and an int8
    cache; return both plus the ragged metadata."""
    rng = np.random.default_rng(seed)
    n = len(specs)
    cu = np.zeros(s_max + 1, np.int32)
    q_lens = np.zeros(s_max, np.int32)
    seq_lens = np.zeros(s_max, np.int32)
    tables = np.zeros((s_max, max_pages), np.int32)
    num_pages = 1 + sum(-(-c // page) for c, _ in specs) + 1
    dense = init_kv_cache(1, num_pages, page, hkv, d, jnp.float32)
    quant = init_kv_cache(1, num_pages, page, hkv, d, jnp.float32,
                          quantized=True)
    assert cache_is_quantized(quant[0][0])
    total, next_page = 0, 1
    for i, (ctx, qn) in enumerate(specs):
        cu[i] = total
        q_lens[i] = qn
        seq_lens[i] = ctx
        total += align_to_block(qn, TB)
        pn = -(-ctx // page)
        ids = list(range(next_page, next_page + pn))
        next_page += pn
        tables[i, :pn] = ids
        kd = rng.standard_normal((ctx, hkv, d)).astype(np.float32)
        vd = rng.standard_normal((ctx, hkv, d)).astype(np.float32)
        slots = np.asarray(
            [ids[p // page] * page + p % page for p in range(ctx)],
            np.int32)
        dense = _write_tokens(dense, (kd, vd), slots)
        quant = _write_tokens(quant, (kd, vd), slots)
    cu[n:] = total
    t_padded = align_to_block(max(total, TB), TB)
    h = 2 * hkv
    q = np.zeros((t_padded, h, d), np.float32)
    for i, (ctx, qn) in enumerate(specs):
        q[cu[i]: cu[i] + qn] = rng.standard_normal(
            (qn, h, d)).astype(np.float32)
    return (jnp.asarray(q), dense[0], quant[0], jnp.asarray(tables),
            jnp.asarray(cu), jnp.asarray(q_lens), jnp.asarray(seq_lens),
            n)


# ------------------------------------------------------- write op
def test_quant_write_roundtrips_within_half_step():
    hkv, d, page = 2, 32, 4
    (kc, vc), = init_kv_cache(1, 8, page, hkv, d, jnp.float32,
                              quantized=True)
    rng = np.random.default_rng(0)
    x = rng.standard_normal((12, hkv, d)).astype(np.float32)
    slots = np.arange(4, 16, dtype=np.int32)  # pages 1..3
    (kc, vc) = write_kv_cache(kc, vc, jnp.asarray(x), jnp.asarray(x),
                              slots)
    got = np.asarray(gather_pages(kc, jnp.arange(1, 4))).transpose(
        1, 2, 0, 3).reshape(12, hkv, d)
    scales = np.asarray(kc[1])[:, 1:4]  # [Hkv, 3]
    # rounding error of absmax int8: half a step of that page's scale
    err = np.abs(got - x)
    per_page = err.reshape(3, page, hkv, d).max(axis=(1, 3)).T
    assert np.all(per_page <= 0.5 * scales + 1e-6)


def test_quant_write_fresh_page_resets_stale_scale():
    """Page-pool reuse: a page that once held huge values must not keep
    its large scale when a new sequence writes small values from offset
    0 — the stale-scale leak would quantize the new tokens to garbage."""
    hkv, d, page = 2, 32, 4
    (kc, vc), = init_kv_cache(1, 4, page, hkv, d, jnp.float32,
                              quantized=True)
    big = np.full((page, hkv, d), 100.0, np.float32)
    slots = np.arange(page, 2 * page, dtype=np.int32)  # page 1
    kc, vc = write_kv_cache(kc, vc, jnp.asarray(big), jnp.asarray(big),
                            slots)
    assert np.asarray(kc[1])[0, 1] > 0.5
    small = np.full((page, hkv, d), 0.01, np.float32)
    kc, vc = write_kv_cache(kc, vc, jnp.asarray(small),
                            jnp.asarray(small), slots)
    new_scale = np.asarray(kc[1])[:, 1]
    assert np.all(new_scale < 1e-3), new_scale
    got = np.asarray(gather_pages(kc, jnp.asarray([1]))).transpose(
        1, 2, 0, 3).reshape(page, hkv, d)
    np.testing.assert_allclose(got, small, atol=1e-4)


def test_quant_write_append_rescales_existing_tokens():
    """Decode append with a larger absmax grows the page scale; the
    already-quantized rows are rescaled in place and stay within half a
    NEW step of their original values."""
    hkv, d, page = 2, 32, 8
    (kc, vc), = init_kv_cache(1, 4, page, hkv, d, jnp.float32,
                              quantized=True)
    rng = np.random.default_rng(3)
    first = rng.standard_normal((4, hkv, d)).astype(np.float32)
    kc, vc = write_kv_cache(kc, vc, jnp.asarray(first), jnp.asarray(first),
                            np.arange(8, 12, dtype=np.int32))
    loud = 5.0 * rng.standard_normal((4, hkv, d)).astype(np.float32)
    kc, vc = write_kv_cache(kc, vc, jnp.asarray(loud), jnp.asarray(loud),
                            np.arange(12, 16, dtype=np.int32))
    got = np.asarray(gather_pages(kc, jnp.asarray([1]))).transpose(
        1, 2, 0, 3).reshape(page, hkv, d)
    scale = np.asarray(kc[1])[:, 1]  # [Hkv]
    bound = (scale + 1e-6)[None, :, None]  # re-rounding: one full step
    assert np.all(np.abs(got[:4] - first) <= bound)
    assert np.all(np.abs(got[4:] - loud) <= 0.5 * bound + 1e-6)


# ------------------------------------------------------- attention oracle
CASES = {
    "mixed": [(24, 9), (1, 1), (13, 13), (30, 1)],
    "decode_only": [(9, 1), (4, 1), (14, 1)],
    "prefill_only": [(16, 16), (11, 11)],
    # tests/ops/test_ragged_paged_attention.py's case of the same name:
    # six 8-key KV blocks of two pages, each page under its own scale,
    # the last block ragged; behind a decode row, so the narrow path
    # reads another sequence's scales in the same q block
    "kv_blocks_ragged_tail": [(9, 1), (45, 19)],
}
#: the kernel's tile where a case pins it (q_block, pages_per_block)
TILES = {"kv_blocks_ragged_tail": dict(q_block=16, pages_per_block=2)}


@pytest.mark.parametrize("name", sorted(CASES))
@pytest.mark.parametrize("use_pallas", [False, True])
def test_ragged_quant_kernel_matches_quant_ref(name, use_pallas):
    """Kernel-side in-loop dequant (per-page scales on the score and
    probability columns) == reference gather-dequant on the same int8
    cache."""
    hkv, d, page = 2, 32, 4
    (q, _, quant, tables, cu, q_lens, seq_lens, n) = _build_pair(
        CASES[name], hkv, d, page, s_max=6, max_pages=12,
        seed=sum(map(ord, name)) % 89)
    kq, vq = quant
    got = ragged_paged_attention(q, kq, vq, tables, cu, q_lens,
                                 seq_lens, n, use_pallas=use_pallas,
                                 **TILES.get(name, {}))
    ref = ragged_paged_attention_ref(q, kq, vq, tables, cu, q_lens,
                                     seq_lens, n)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("name", ["mixed", "kv_blocks_ragged_tail"])
@pytest.mark.parametrize("hkv", [1, 2, 4, 8])
def test_ragged_quant_kernel_reads_each_heads_own_scales(hkv, name):
    """ISSUE 49: a grid cell holds every kv head, and its scale block is
    ``[Hkv * nsub, max_pages]``, a row a (head, sub-block).  The heads'
    scales are spread over a factor of nine here, so a head that read
    another head's row, or another sub-block's, would be far off."""
    (q, _, quant, tables, cu, q_lens, seq_lens, n) = _build_pair(
        CASES[name], hkv, 32, 4, s_max=6, max_pages=12, seed=23 + hkv)
    gain = jnp.asarray(1 + 8 * np.arange(hkv) / max(hkv - 1, 1),
                       jnp.float32)[:, None]
    kq, vq = ((data, scale * gain) for data, scale in quant)
    got = ragged_paged_attention(q, kq, vq, tables, cu, q_lens, seq_lens,
                                 n, use_pallas=True, **TILES.get(name, {}))
    ref = ragged_paged_attention_ref(q, kq, vq, tables, cu, q_lens,
                                     seq_lens, n)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-5, atol=1e-4)


@pytest.mark.parametrize("name", sorted(CASES))
def test_ragged_quant_vs_f32_oracle_per_head_bounds(name):
    """Quantized attention vs the full-precision cache, bounded PER
    KV-HEAD by the scales actually in that head's pages: value error
    contributes <= step/2 of the head's largest V scale; key error
    perturbs scores by <= |q|_1 * step/2, which softmax turns into a
    bounded reweighting of rows whose spread the output inherits."""
    hkv, d, page = 2, 32, 4
    (q, dense, quant, tables, cu, q_lens, seq_lens, n) = _build_pair(
        CASES[name], hkv, d, page, s_max=6, max_pages=12, seed=17)
    kd, vd = dense
    kq, vq = quant
    want = np.asarray(ragged_paged_attention_ref(
        q, kd, vd, tables, cu, q_lens, seq_lens, n))
    got = np.asarray(ragged_paged_attention_ref(
        q, kq, vq, tables, cu, q_lens, seq_lens, n))
    err = np.abs(got - want)  # [T, H, D]
    h = q.shape[1]
    group = h // hkv
    k_sc = np.asarray(kq[1])
    v_sc = np.asarray(vq[1])
    for kvh in range(hkv):
        half_v = 0.5 * float(v_sc[kvh].max())
        half_k = 0.5 * float(k_sc[kvh].max())
        # row spread of V bounds what a softmax reweighting can move;
        # with unit-normal V the spread is a few sigma — take the
        # empirical spread of the oracle output plus the direct V term
        spread = float(np.abs(want[:, kvh * group:(kvh + 1) * group])
                       .max()) + 3.0
        scale = 1.0 / np.sqrt(d)
        q_l1 = float(np.abs(np.asarray(q)).sum(axis=-1).max()) * scale
        bound = half_v + 2.0 * q_l1 * half_k * spread
        head_err = float(err[:, kvh * group:(kvh + 1) * group].max())
        assert head_err <= bound, (kvh, head_err, bound)
        # engineering sanity: quantization error stays small in absolute
        # terms on unit-normal activations
        assert head_err < 0.25, (kvh, head_err)


@pytest.mark.parametrize("use_pallas", [False, True])
def test_decode_quant_matches_quant_ref(use_pallas):
    hkv, d, page = 2, 128, 8
    h = 2 * hkv
    rng = np.random.default_rng(5)
    num_pages, b = 6, 3
    (kc, vc), = init_kv_cache(1, num_pages, page, hkv, d, jnp.float32,
                              quantized=True)
    ctx_lens = np.asarray([13, 8, 5], np.int32)
    tables = np.zeros((b, 4), np.int32)
    next_page = 1
    for i, ctx in enumerate(ctx_lens):
        pn = -(-int(ctx) // page)
        ids = list(range(next_page, next_page + pn))
        next_page += pn
        tables[i, :pn] = ids
        x = rng.standard_normal((int(ctx), hkv, d)).astype(np.float32)
        y = rng.standard_normal((int(ctx), hkv, d)).astype(np.float32)
        slots = np.asarray([ids[p // page] * page + p % page
                            for p in range(int(ctx))], np.int32)
        kc, vc = write_kv_cache(kc, vc, jnp.asarray(x), jnp.asarray(y),
                                slots)
    q = rng.standard_normal((b, h, d)).astype(np.float32)
    got = paged_attention(jnp.asarray(q), kc, vc, jnp.asarray(tables),
                          jnp.asarray(ctx_lens), use_pallas=use_pallas)
    ref = paged_attention_ref(jnp.asarray(q), kc, vc,
                              jnp.asarray(tables), jnp.asarray(ctx_lens))
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


# ------------------------------------------------------- autotune
def test_autotune_runs_per_layout():
    """The (q_block, pages_per_block, dma_slots) search keys on the
    layout: the int8 layout's budget halves the KV buffers and adds a
    float staging copy, so the two layouts are distinct lru entries
    (and may pick differently)."""
    base = auto_ragged_blocks(128, 16)
    quant = auto_ragged_blocks(128, 16, quantized=True, kv_itemsize=1)
    assert isinstance(base, tuple) and isinstance(quant, tuple)
    info = auto_ragged_blocks.cache_info()
    # both layouts cached independently; repeat calls hit
    auto_ragged_blocks(128, 16)
    auto_ragged_blocks(128, 16, quantized=True, kv_itemsize=1)
    info2 = auto_ragged_blocks.cache_info()
    assert info2.hits >= info.hits + 2


# ------------------------------------------------------- capacity math
@pytest.mark.parametrize("hkv,page,d", [(2, 4, 32), (8, 16, 128)])
def test_int8_page_pool_at_least_1p8x_bf16(hkv, page, d):
    bf16 = page_bytes(hkv, page, d, quantized=False, itemsize=2)
    int8 = page_bytes(hkv, page, d, quantized=True)
    assert bf16 / int8 >= 1.8
    budget = 1 << 24
    dense_pages = pages_for_budget(budget, 4, hkv, page, d,
                                   quantized=False, itemsize=2)
    quant_pages = pages_for_budget(budget, 4, hkv, page, d,
                                   quantized=True)
    assert quant_pages >= 1.8 * dense_pages
    assert bytes_per_token(4, hkv, page, d, quantized=True) \
        < bytes_per_token(4, hkv, page, d, quantized=False, itemsize=2)


# ------------------------------------------------------- wire helpers
def test_quantize_payload_roundtrip_and_trim_concat():
    rng = np.random.default_rng(7)
    page = 4
    payload = [(rng.standard_normal((2, 11, 8)).astype(np.float32),
                rng.standard_normal((2, 11, 8)).astype(np.float32))
               for _ in range(2)]
    wire = quantize_payload(payload, page)
    assert is_quant_payload(wire) and not is_quant_payload(payload)
    assert payload_seq_len(wire) == 11
    back = dequantize_payload(wire, page)
    for (k, v), (k2, v2), ((kq, ks), _) in zip(payload, back, wire):
        # bound each token's error by ITS page's half-step
        steps = np.repeat(ks, page, axis=1)[:, :k.shape[1]]  # [Hkv, S]
        assert np.all(np.abs(k - k2) <= 0.5 * steps[..., None] + 1e-6)
    # trim keeps ceil(use/page) scale columns
    t = trim_payload(wire, 6, page)
    assert t[0][0][0].shape[1] == 6 and t[0][0][1].shape[1] == 2
    # page-aligned concat round-trips exactly (no requantization)
    a = trim_payload(wire, 8, page)
    b = [((kq[:, 8:], ks[:, 2:]), (vq[:, 8:], vs[:, 2:]))
         for (kq, ks), (vq, vs) in wire]
    cat = concat_payloads([a, b], page)
    for i in range(2):
        np.testing.assert_array_equal(cat[i][0][0], wire[i][0][0])
        np.testing.assert_array_equal(cat[i][0][1], wire[i][0][1])
    # quantizing an already-quantized payload is a no-op (identity)
    assert quantize_payload(wire, page) is wire
