import importlib

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

# the module, not the op of the same name that ``ops`` exports
_pa = importlib.import_module("vllm_omni_tpu.ops.paged_attention")
PAGE = 16


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


def _row_by_row(cache, new, slots):
    """The write's contract in plain numpy: row ``t`` lands at page
    ``slot // page``, offset ``slot % page``; a negative slot is dropped."""
    out = np.array(cache)
    for t, s in enumerate(np.asarray(slots)):
        if s >= 0:
            out[:, s // PAGE, s % PAGE] = np.asarray(new)[t]
    return out


def _decode_batch(rs, pages):
    """One row a sequence, each in a page of its own at any offset."""
    return rs.permutation(pages)[:8] * PAGE + rs.integers(0, PAGE, 8)


def _chunk_mid_page(rs, pages):
    """A prefill chunk that starts mid-page and crosses two page
    boundaries, its pages anywhere in the pool, then two decode rows."""
    p = rs.permutation(pages)
    chunk = (p[:3, None] * PAGE + np.arange(PAGE)).reshape(-1)[11:37]
    return np.concatenate([chunk, p[3:5] * PAGE + [0, 15], [-1] * 4])


def _dropped_among_real(rs, pages):
    slots = _chunk_mid_page(rs, pages)
    slots[[0, 5, 6, 17, 27]] = -1
    return slots


def _all_padding(rs, pages):
    return np.full(16, -1)


def _shuffled(rs, pages):
    """Distinct slots in no order: a page's rows are not adjacent, and
    more tokens than one block of the page-write kernel takes, so pages
    are shared between blocks."""
    slots = rs.permutation(pages * PAGE)[:150]
    slots[::9] = -1
    return slots


STEPS = {f.__name__[1:]: f for f in (
    _decode_batch, _chunk_mid_page, _dropped_among_real, _all_padding,
    _shuffled)}
# how a step's rows reach the pool: as served here (off the TPU the XLA
# row scatter) and through the page-write kernel, interpreted
WRITES = {"served": write_kv_cache, "pages_kernel": _pa._write_pages}


def _pools_and_rows(hkv, d, pages, t, dtype, seed=0):
    rs = np.random.default_rng(seed)
    return [jnp.asarray(rs.standard_normal(shape), dtype)
            for shape in [(hkv, pages, PAGE, d)] * 2 + [(t, hkv, d)] * 2]


@pytest.mark.parametrize("write", sorted(WRITES))
@pytest.mark.parametrize("step", sorted(STEPS))
@pytest.mark.parametrize("d", [128, 256])
@pytest.mark.parametrize("hkv", [1, 2, 4])
def test_write_equals_row_by_row_reference(hkv, d, step, write):
    pages = 12
    slots = STEPS[step](np.random.default_rng(5), pages).astype(np.int32)
    kc, vc, kn, vn = _pools_and_rows(hkv, d, pages, len(slots),
                                     jnp.bfloat16)
    got = WRITES[write](kc, vc, kn, vn, jnp.asarray(slots))
    for g, cache, new in zip(got, (kc, vc), (kn, vn)):
        assert g.dtype == cache.dtype and g.shape == cache.shape
        np.testing.assert_array_equal(
            np.asarray(g, np.float32),
            _row_by_row(cache, new, slots).astype(np.float32))


@pytest.mark.parametrize("write", sorted(WRITES))
def test_write_casts_rows_to_a_float32_pool(write):
    """A float32 pool (the CPU engines' caches) takes bfloat16 rows
    exactly; the kernel's page is then two (8, 128) tiles high."""
    slots = _dropped_among_real(np.random.default_rng(6), 6).astype(np.int32)
    kc, vc, kn, vn = _pools_and_rows(2, 128, 6, len(slots), jnp.float32)
    kn, vn = kn.astype(jnp.bfloat16), vn.astype(jnp.bfloat16)
    got = WRITES[write](kc, vc, kn, vn, jnp.asarray(slots))
    for g, cache, new in zip(got, (kc, vc), (kn, vn)):
        np.testing.assert_array_equal(
            np.asarray(g), _row_by_row(cache, new.astype(jnp.float32), slots))


def test_pages_kernel_keeps_the_later_row_of_a_slot_written_twice():
    """Blocks run in order and a block lays its rows in token order, so
    the kernel is the row-by-row loop even where the mapping repeats a
    slot (an XLA scatter promises nothing there)."""
    slots = np.array([33, 34, 35, -1, 34, 200, 33, 35], np.int32)
    slots = np.concatenate([slots, np.full(140, -1), [34]]).astype(np.int32)
    kc, vc, kn, vn = _pools_and_rows(2, 128, 16, len(slots), jnp.bfloat16)
    got_k, _ = _pa._write_pages(kc, vc, kn, vn, jnp.asarray(slots))
    np.testing.assert_array_equal(
        np.asarray(got_k, np.float32),
        _row_by_row(kc, kn, slots).astype(np.float32))


@pytest.mark.parametrize("step", ["chunk_mid_page", "all_padding"])
def test_write_under_jit_with_the_pool_donated(step):
    """As a served step runs it: the pools donated to the jitted program.
    The result is the undonated one, and where the backend honours the
    donation the output is the input's buffer: the write is in place."""
    pages = 12
    slots = STEPS[step](np.random.default_rng(7), pages).astype(np.int32)
    kc, vc, kn, vn = _pools_and_rows(4, 128, pages, len(slots), jnp.bfloat16)
    want = [_row_by_row(c, n, slots).astype(np.float32)
            for c, n in ((kc, kn), (vc, vn))]
    where = [c.unsafe_buffer_pointer() for c in (kc, vc)]
    step_fn = jax.jit(write_kv_cache, donate_argnums=(0, 1))
    got = step_fn(kc, vc, kn, vn, jnp.asarray(slots))
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g, np.float32), w)
    if kc.is_deleted() and vc.is_deleted():  # the donation was taken
        assert [g.unsafe_buffer_pointer() for g in got] == where


def test_write_plan_names_runs_pages_and_buffers():
    """The page-write kernel's plan for one 16-token block: runs of
    consecutive slots inside a page, each distinct page held once
    however its runs are ordered, every run pointed at its page's
    buffer."""
    #        page 2: 3 rows | page 5, 1 | dropped | page 2 again | page 3
    slots = jnp.asarray([37, 38, 39, 80, -1, -1, 32, 33, 47, 48, 49]
                        + [-1] * 5, jnp.int32)
    run_len, buf, held, n_pages = _pa._write_plan(slots, PAGE, 16)
    assert list(run_len[:11]) == [3, 0, 0, 1, 0, 0, 2, 0, 1, 2, 0]
    starts = [0, 3, 6, 8, 9]
    assert [int(buf[i]) for i in starts] == [0, 1, 0, 0, 2]
    assert list(n_pages) == [3] and list(held[:3]) == [2, 5, 3]


def test_write_dispatch_names_the_geometry_it_cannot_tile():
    """A page of 8 bfloat16 rows is half a packed tile: the page-write
    kernel is refused there (and the row scatter serves), as for a head
    width off the lanes."""
    bf = jnp.zeros((2, 4, 16, 128), jnp.bfloat16)
    assert _pa.write_tiling_reason(bf) == ""
    assert _pa.write_tiling_reason(jnp.zeros((2, 4, 8, 128))) == ""
    assert "page_size 8 % 16" in _pa.write_tiling_reason(
        jnp.zeros((2, 4, 8, 128), jnp.bfloat16))
    assert "head_dim 64" in _pa.write_tiling_reason(
        jnp.zeros((2, 4, 16, 64), jnp.bfloat16))


def test_write_dispatch_counts_every_buffer_against_vmem():
    """A grid step's tokens follow the page's size, with the float32
    rows and the double-buffered block counted beside the page buffers;
    a page of which sixteen do not fit, and a step whose plans overrun
    SMEM, are refused by name."""
    cells = jnp.zeros((4, 4, 16, 128), jnp.bfloat16)
    assert _pa._write_block(cells) == 128
    assert _pa._write_block(jnp.zeros((2, 4, 16, 256), jnp.bfloat16)) == 128
    wide = jnp.zeros((8, 2, 16, 256), jnp.float32)
    assert _pa._write_block(wide) == 16
    assert _pa.write_tiling_reason(wide, 2048) == ""
    huge = jnp.zeros((8, 2, 128, 128), jnp.bfloat16)
    assert "bytes of VMEM" in _pa.write_tiling_reason(huge, 2048)
    assert _pa.write_tiling_reason(cells, 32768) == ""
    assert "SMEM" in _pa.write_tiling_reason(cells, 65536)


@pytest.mark.parametrize("d", [128, 256])
@pytest.mark.parametrize("page,kernel", [(16, True), (8, False)])
def test_write_dispatch_on_a_platform_that_compiles_the_kernel(
        page, kernel, d, monkeypatch):
    """There a step over 256 rows a half goes to the kernel at either
    head width; a pool the kernel cannot take (a page of 8 bfloat16 rows)
    is written by the row scatter, and the fallback is counted."""
    from vllm_omni_tpu.ops import _dispatch
    monkeypatch.setattr(_dispatch, "pallas_mode", lambda: "native")
    monkeypatch.setattr(_dispatch, "native_fallbacks",
                        _dispatch.native_fallbacks.copy())
    taken = []
    monkeypatch.setattr(
        _pa, "_write_pages",
        lambda kc, vc, *rest: taken.append(kc.shape) or (kc, vc))
    rs = np.random.default_rng(9)
    slots = rs.permutation(192)[:160].astype(np.int32)
    slots[::7] = -1
    kc = jnp.asarray(rs.standard_normal((2, 192 // page, page, d)),
                     jnp.bfloat16)
    kn = jnp.asarray(rs.standard_normal((160, 2, d)), jnp.bfloat16)
    before = sum(_dispatch.native_fallbacks.values())
    # the undecorated function: a jit cache would remember another mode
    got_k, _ = write_kv_cache.__wrapped__(kc, kc, kn, kn, jnp.asarray(slots))
    assert taken == ([kc.shape] if kernel else [])
    assert sum(_dispatch.native_fallbacks.values()) - before == (not kernel)
    if not kernel:
        want = np.array(kc, np.float32).reshape(2, -1, d)
        keep = slots >= 0
        want[:, slots[keep]] = np.moveaxis(
            np.asarray(kn, np.float32)[keep], 1, 0)
        np.testing.assert_array_equal(
            np.asarray(got_k, np.float32).reshape(2, -1, d), want)


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


# the decode entry: one token a segment through the ragged kernel's KV
# blocks, each row's first block started by the row before it
DECODE_POOLS = {
    # the thinker: 8 query heads a kv head, two pools
    "group8_w128": dict(hkv=2, group=8, d=128),
    # GLM's latent cache: 20 heads on one shared pool of 640-lane rows,
    # the value its first 512 lanes (24 rows a tile inside)
    "group20_shared_w640": dict(hkv=1, group=20, d=640, v_dim=512),
    # Qwen3-Next's gated attention
    "w256_2kv": dict(hkv=2, group=8, d=256),
    "int8": dict(hkv=2, group=8, d=128, quantized=True),
    # ISSUE 49: a row's tile holds every kv head (LFM2 and the thinker 4,
    # granite 8 with its group of 4 padded to a sublane tile; an int8
    # scale a (head, page))
    "group8_4kv": dict(hkv=4, group=8, d=128),
    "group4_8kv": dict(hkv=8, group=4, d=128),
    "int8_4kv": dict(hkv=4, group=2, d=128, quantized=True),
}
BLOCK = 32 * PAGE  # keys a KV block (16 pages at float32 rows of 256+)
DECODE_CONTEXTS = {
    # the edges of a page and of a KV block: one key, one page, one
    # block exactly (of 16 pages, of 32), a page more
    "edges": [1, PAGE, BLOCK // 2, BLOCK, BLOCK + PAGE],
    # nothing may be started for, or waited by, a row without context:
    # one between two real rows, one as the last row
    "empty_rows": [40, 0, 77, 0],
    "one_row": [100],
    # a row more than a grid cell's sixteen
    "seventeen_rows": [7 + 11 * i for i in range(17)],
}


def _decode_batch_of(pool, contexts):
    """(q, k, v, tables, lens, v_dim, hkv, group) of a decode batch."""
    geo, lens = DECODE_POOLS[pool], DECODE_CONTEXTS[contexts]
    hkv, group, d = geo["hkv"], geo["group"], geo["d"]
    v_dim, quantized = geo.get("v_dim"), geo.get("quantized", False)
    b, rs = len(lens), np.random.default_rng(len(pool) + len(contexts))
    pages_per = [-(-n // PAGE) for n in lens]
    tables = np.zeros((b, max(pages_per) + 1), np.int32)
    ids = 1 + rs.permutation(sum(pages_per))  # scattered, page 0 unused
    used = 0
    for i, n in enumerate(pages_per):
        tables[i, :n] = ids[used: used + n]
        used += n

    def half():
        x = rs.standard_normal((hkv, used + 1, PAGE, d)).astype(np.float32)
        if not quantized:
            return jnp.asarray(x)
        scale = np.abs(x).max(axis=(2, 3)) / 127
        return (jnp.asarray(np.round(x / scale[..., None, None]), jnp.int8),
                jnp.asarray(scale))

    k, v = half(), None if v_dim else half()
    q = jnp.asarray(rs.standard_normal((b, hkv * group, d)), jnp.float32)
    return (q, k, v, jnp.asarray(tables), jnp.asarray(lens, jnp.int32),
            v_dim, hkv, group)


@pytest.mark.parametrize("contexts", sorted(DECODE_CONTEXTS))
@pytest.mark.parametrize("pool", sorted(DECODE_POOLS))
def test_decode_entry_matches_the_reference(pool, contexts):
    q, k, v, tables, lens, v_dim, hkv, group = _decode_batch_of(
        pool, contexts)
    b, d = q.shape[0], q.shape[-1]
    got = np.asarray(paged_attention(q, k, v, tables, lens,
                                     use_pallas=True, v_dim=v_dim))
    want = np.asarray(paged_attention_ref(q, k, v, tables, lens,
                                          v_dim=v_dim))
    assert got.shape == want.shape == (b, hkv * group, v_dim or d)
    real = np.asarray(lens) > 0
    # a float32 pool is computed in float32 (int8 rows are exact in it)
    np.testing.assert_allclose(got[real], want[real], atol=2e-5)
    assert np.all(got[~real] == 0.0)


@pytest.mark.parametrize("contexts", ["edges", "empty_rows",
                                      "seventeen_rows"])
@pytest.mark.parametrize("pool", sorted(
    p for p, geo in DECODE_POOLS.items() if geo["hkv"] > 1))
def test_decode_entry_gives_each_kv_head_what_it_gives_alone(pool, contexts):
    """ISSUE 49: a row's tile attends every kv head against a KV block
    that came in once.  Head ``h`` of it equals, exactly, the entry on a
    pool of that head alone, which is the parent's grid cell of the head
    (the same KV blocks, the same order, the same hand-off to the next
    row)."""
    q, k, v, tables, lens, v_dim, hkv, group = _decode_batch_of(
        pool, contexts)

    def one(cache, head):
        if isinstance(cache, tuple):
            return tuple(x[head: head + 1] for x in cache)
        return cache[head: head + 1]

    got = np.asarray(paged_attention(q, k, v, tables, lens,
                                     use_pallas=True))
    for head in range(hkv):
        heads = slice(head * group, (head + 1) * group)
        alone = paged_attention(q[:, heads], one(k, head), one(v, head),
                                tables, lens, use_pallas=True)
        np.testing.assert_array_equal(got[:, heads], np.asarray(alone))


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


# ------------------------------------------------ the shared (latent) layout
def test_init_allocates_one_pool_for_the_shared_layout():
    (pool, value), = init_kv_cache(1, 4, PAGE, 1, 640, jnp.bfloat16,
                                   shared=True)
    assert value is None and pool.shape == (1, 4, PAGE, 640)
    with pytest.raises(ValueError, match="no int8 form"):
        init_kv_cache(1, 4, PAGE, 1, 640, quantized=True, shared=True)


@pytest.mark.parametrize("write", sorted(WRITES))
@pytest.mark.parametrize("step", sorted(STEPS))
def test_shared_pool_write_equals_row_by_row_reference(step, write):
    """One pool of 640-lane rows (a latent cache's [c_kv | k_rope | pad])
    through the same write: the value absent, one row a token."""
    pages = 12
    slots = STEPS[step](np.random.default_rng(5), pages).astype(np.int32)
    pool, _, rows, _ = _pools_and_rows(1, 640, pages, len(slots),
                                       jnp.bfloat16)
    got = WRITES[write](pool, None, rows, None, jnp.asarray(slots))
    assert len(got) == (2 if write == "served" else 1)
    assert write != "served" or got[1] is None
    assert got[0].dtype == pool.dtype and got[0].shape == pool.shape
    np.testing.assert_array_equal(
        np.asarray(got[0], np.float32),
        _row_by_row(pool, rows, slots).astype(np.float32))


def test_shared_pool_write_takes_the_kernel_on_a_platform_that_compiles_it(
        monkeypatch):
    """A chunk's 300 rows go through the page kernel at one kv head too
    (its buffers counted for one pool: 128 tokens a block), a decode
    batch's through the row scatter; neither is counted as a fallback."""
    from vllm_omni_tpu.ops import _dispatch
    monkeypatch.setattr(_dispatch, "pallas_mode", lambda: "native")
    monkeypatch.setattr(_dispatch, "native_fallbacks",
                        _dispatch.native_fallbacks.copy())
    monkeypatch.setattr(_pa, "interpret_flag", lambda: True)
    calls = []
    real = _pa._write_pages
    monkeypatch.setattr(_pa, "_write_pages",
                        lambda *a: calls.append(a[1]) or real(*a))
    pool = jnp.zeros((1, 24, PAGE, 640), jnp.bfloat16)
    assert _pa._write_block(pool, 1) == 128
    assert _pa.write_tiling_reason(pool, 2048, 1) == ""
    for t, kernel in ((300, True), (32, False)):
        rs = np.random.default_rng(t)
        slots = rs.permutation(24 * PAGE)[:t].astype(np.int32)
        rows = jnp.asarray(rs.standard_normal((t, 1, 640)), jnp.bfloat16)
        n = len(calls)
        # not the jitted entry point: the patched kernel must be reached
        got, none = _pa.write_kv_cache.__wrapped__(
            pool, None, rows, None, jnp.asarray(slots))
        assert none is None and (len(calls) > n) == kernel
        assert calls[n:] == [None] * kernel
        np.testing.assert_array_equal(
            np.asarray(got, np.float32),
            _row_by_row(pool, rows, slots).astype(np.float32))
    assert not _dispatch.native_fallbacks


def _latent_case(seed, b, ctx_lens, heads=20, width=640, v_dim=512):
    """A shared pool filled for ``b`` sequences of the given contexts,
    each row [576 numbers | zeros], and one absorbed query a head."""
    rs = np.random.default_rng(seed)
    pages_per = [-(-c // PAGE) for c in ctx_lens]
    pool = np.zeros((1, 1 + sum(pages_per), PAGE, width), np.float32)
    tables = np.zeros((b, max(pages_per) + 1), np.int32)
    nxt = 1
    for i, n in enumerate(pages_per):
        tables[i, :n] = np.arange(nxt, nxt + n)
        nxt += n
    pool[0, 1:, :, :576] = rs.standard_normal(pool[0, 1:, :, :576].shape)
    q = rs.standard_normal((b, heads, width)).astype(np.float32)
    q[..., 576:] = 0
    return (jnp.asarray(q), jnp.asarray(pool), jnp.asarray(tables),
            jnp.asarray(ctx_lens, jnp.int32), v_dim)


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5),
                                       (jnp.bfloat16, 2e-2)])
def test_shared_pool_decode_kernel_matches_the_reference(dtype, tol):
    """The decode entry over one pool of 640-lane rows whose first 512
    lanes are the value, 20 query heads on the one kv head (padded to 24
    rows inside): against the gather reference, and against the plain
    definition with the value taken from the key."""
    q, pool, tables, lens, v_dim = _latent_case(3, 3, [40, 1, 77])
    q, pool = q.astype(dtype), pool.astype(dtype)
    ref = paged_attention_ref(q, pool, None, tables, lens, 1 / 16, v_dim)
    got = paged_attention(q, pool, None, tables, lens, scale=1 / 16,
                          use_pallas=True, v_dim=v_dim)
    assert got.shape == ref.shape == (3, 20, 512)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(ref, np.float32), atol=tol)
    # the definition, for the second sequence's neighbour (77 keys)
    keys = np.asarray(pool, np.float32)[0, np.asarray(tables[2])].reshape(
        -1, 640)[:77]
    s = np.asarray(q, np.float32)[2] @ keys.T / 16
    p = np.exp(s - s.max(-1, keepdims=True))
    want = (p / p.sum(-1, keepdims=True)) @ keys[:, :512]
    np.testing.assert_allclose(np.asarray(ref, np.float32)[2], want,
                               atol=tol)


def test_shared_pool_dispatch_names_a_value_off_the_lanes(monkeypatch):
    """The tiny models' latent width (64) is no lane tile: the ops say so
    and take the reference, counted, on a platform that compiles them."""
    assert _pa.shared_tiling_reason(None, 512) == ""
    assert _pa.shared_tiling_reason((), 64) == ""  # not shared: no claim
    assert "value width 64" in _pa.shared_tiling_reason(None, 64)
    assert "head_dim 576" in _pa.paged_tiling_reason(576, 16)
    assert _pa.paged_tiling_reason(640, 16) == ""
