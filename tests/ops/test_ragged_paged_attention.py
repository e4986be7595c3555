"""Ragged paged attention: kernel vs XLA reference oracle across ragged
shapes — mixed decode + prefill chunks, GQA, empty sequences, 1-token
decode rows, page-boundary and q-block-boundary lengths — all in Pallas
interpret mode on CPU (conftest sets OMNI_TPU_PALLAS_INTERPRET=1)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from vllm_omni_tpu.ops import (
    attention_ref,
    ragged_paged_attention,
    ragged_paged_attention_ref,
    write_kv_cache,
)
from vllm_omni_tpu.ops.paged_attention import init_kv_cache
from vllm_omni_tpu.ops.ragged_paged_attention import (
    _block_owners,
    align_to_block,
    wide_q_tokens,
)

TB = 8  # DEFAULT_TOKEN_BLOCK: the packing alignment


def _pack(specs, h, hkv, d, page, s_max, max_pages, seed=0):
    """Build a token-packed ragged batch from per-seq (ctx_len, q_len)
    specs.  Returns (q, k_cache, v_cache, page_tables, cu_q_lens,
    q_lens, seq_lens, num_seqs, dense) where ``dense`` holds each
    sequence's full dense K/V [ctx, Hkv, D] for the oracle."""
    rng = np.random.default_rng(seed)
    n = len(specs)
    assert n <= s_max
    cu = np.zeros(s_max + 1, np.int32)
    q_lens = np.zeros(s_max, np.int32)
    seq_lens = np.zeros(s_max, np.int32)
    tables = np.zeros((s_max, max_pages), np.int32)
    total = 0
    next_page = 1  # page 0 stays unused: catches stray page-0 reads
    num_pages = 1 + sum(-(-c // page) for c, _ in specs) + 1
    (kc, vc), = init_kv_cache(1, num_pages, page, hkv, d, jnp.float32)
    dense = []
    for i, (ctx, qn) in enumerate(specs):
        assert qn <= ctx
        cu[i] = total
        q_lens[i] = qn
        seq_lens[i] = ctx
        total += align_to_block(qn, TB)
        pn = -(-ctx // page)
        ids = list(range(next_page, next_page + pn))
        next_page += pn
        tables[i, :pn] = ids
        k_dense = rng.standard_normal((ctx, hkv, d)).astype(np.float32)
        v_dense = rng.standard_normal((ctx, hkv, d)).astype(np.float32)
        dense.append((k_dense, v_dense))
        slots = np.asarray(
            [ids[p // page] * page + p % page for p in range(ctx)],
            np.int32)
        kc, vc = write_kv_cache(kc, vc, jnp.asarray(k_dense),
                                jnp.asarray(v_dense), jnp.asarray(slots))
    cu[n:] = total
    t_padded = align_to_block(max(total, TB), TB)
    q = np.zeros((t_padded, h, d), np.float32)
    for i, (ctx, qn) in enumerate(specs):
        q[cu[i]: cu[i] + qn] = rng.standard_normal(
            (qn, h, d)).astype(np.float32)
    return (jnp.asarray(q), kc, vc, jnp.asarray(tables),
            jnp.asarray(cu), jnp.asarray(q_lens), jnp.asarray(seq_lens),
            n, dense)


def _oracle(q, cu, q_lens, seq_lens, dense, h, d):
    """Per-sequence dense causal attention (attention_ref with the
    cached prefix as leading keys) laid back into the packed rows."""
    out = np.zeros((q.shape[0], h, d), np.float32)
    for i, (k_dense, v_dense) in enumerate(dense):
        qn = int(q_lens[i])
        if qn == 0:
            continue
        lo = int(cu[i])
        ctx = int(seq_lens[i])
        # suffix alignment: queries are the LAST qn positions of ctx
        o = attention_ref(
            jnp.asarray(q)[None, lo: lo + qn],
            jnp.asarray(k_dense[:ctx])[None],
            jnp.asarray(v_dense[:ctx])[None],
            causal=True,
        )[0]
        out[lo: lo + qn] = np.asarray(o)
    return out


# name -> per-sequence (ctx_len, q_len) specs, or a dict that also sets
# the geometry and the kernel's tile (q_block tokens a grid cell,
# pages_per_block pages a loop iteration); the plain lists run at the
# tile ``auto_ragged_blocks`` derives
CASES = {
    "mixed": [(24, 9), (1, 1), (13, 13), (30, 1)],
    "decode_only": [(9, 1), (4, 1), (14, 1)],
    "prefill_only": [(16, 16), (11, 11)],
    "chunk_resume": [(20, 5), (17, 12)],   # later chunks of a prefill
    "page_boundary": [(8, 8), (16, 1), (4, 4)],   # page=4 multiples
    "block_boundary": [(8, 8), (24, 16), (9, 9)],  # q-block multiples +1
    "single": [(5, 5)],
    # a chunk over prior context: six 8-key KV blocks, the last ragged
    # (45 = 5 * 8 + 5), under one whole and one partial 16-token q tile
    "kv_blocks_ragged_tail": dict(
        specs=[(45, 19)], q_block=16, pages_per_block=2),
    # a chunk that starts off a q-block boundary behind two decode
    # rows: the first 32-token block holds three sequences (sub-block
    # by sub-block), the second lies inside the chunk (one tile)
    "wide_block_three_seqs": dict(
        specs=[(9, 1), (14, 1), (40, 40)], q_block=32,
        pages_per_block=2),
    # the first q tile's causal end (key 16 of 80) lies many pages
    # before the context's: its loop must stop there
    "causal_skip": dict(
        specs=[(64, 64), (80, 48)], q_block=16, pages_per_block=2,
        max_pages=20),
    # rows = q_block x group: no GQA sharing, and 8 heads a kv head
    "group_1": dict(
        specs=[(24, 9), (7, 1), (30, 22)], h=2, hkv=2, q_block=16,
        pages_per_block=2),
    "group_8": dict(
        specs=[(24, 9), (7, 1), (30, 22)], h=8, hkv=1, q_block=16,
        pages_per_block=2),
}


def _case(name):
    case = CASES[name]
    return dict(case) if isinstance(case, dict) else {"specs": case}


@pytest.mark.parametrize("name", sorted(CASES))
@pytest.mark.parametrize("use_pallas", [False, True])
def test_matches_dense_oracle(name, use_pallas):
    case = _case(name)
    specs = case.pop("specs")
    h, hkv = case.pop("h", 4), case.pop("hkv", 2)
    d, page = 32, 4
    (q, kc, vc, tables, cu, q_lens, seq_lens, n, dense) = _pack(
        specs, h, hkv, d, page, s_max=6,
        max_pages=case.pop("max_pages", 12),
        seed=sum(map(ord, name)) % 97)
    got = ragged_paged_attention(
        q, kc, vc, tables, cu, q_lens, seq_lens, n,
        use_pallas=use_pallas, **case)
    want = _oracle(q, cu, q_lens, seq_lens, dense, h, d)
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-5,
                               atol=2e-5)
    # padding rows (segment tails + trailing) come back exactly zero
    mask = np.zeros(q.shape[0], bool)
    for i in range(n):
        mask[int(cu[i]): int(cu[i]) + int(q_lens[i])] = True
    assert np.all(np.asarray(got)[~mask] == 0.0)


@pytest.mark.parametrize("dma_slots", [2, 3])
def test_mixed_step_with_a_padding_sub_block_between(dma_slots):
    """A mixed step as the runner packs it, and one thing more: decode
    rows first (contexts of one to four 8-key KV blocks, so every
    narrow tile hands its pipeline on to the next, within a q block and
    across two), then a sub-block nobody owns, then a chunk (narrow at
    its head, one wide tile, narrow at its tail).  The tile before the
    padding must start nothing for it, and the chunk's first tile, which
    nothing started, must prime itself."""
    specs = [(9, 1), (30, 1), (1, 1), (17, 1), (25, 1), (60, 37)]
    h, hkv, d, page = 4, 2, 32, 4
    (q, kc, vc, tables, cu, q_lens, seq_lens, n, dense) = _pack(
        specs, h, hkv, d, page, s_max=8, max_pages=16, seed=5)
    # open an 8-row hole before the chunk
    chunk = len(specs) - 1
    lo = int(cu[chunk])
    q = jnp.concatenate([q[:lo], jnp.zeros((TB, h, d), q.dtype), q[lo:]])
    cu = cu.at[chunk:].add(TB)
    got = ragged_paged_attention(
        q, kc, vc, tables, cu, q_lens, seq_lens, n, use_pallas=True,
        q_block=16, pages_per_block=2, dma_slots=dma_slots)
    want = _oracle(q, cu, q_lens, seq_lens, dense, h, d)
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-5,
                               atol=2e-5)
    ref = ragged_paged_attention_ref(
        q, kc, vc, tables, cu, q_lens, seq_lens, n)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)
    assert np.all(np.asarray(got)[lo: lo + TB] == 0.0)


@pytest.mark.parametrize("use_pallas", [False, True])
def test_gqa_group_wider(use_pallas):
    """H == 4 * Hkv: every kv head serves 4 query heads in one block."""
    h, hkv, d, page = 8, 2, 32, 8
    specs = [(17, 17), (9, 1), (25, 10)]
    (q, kc, vc, tables, cu, q_lens, seq_lens, n, dense) = _pack(
        specs, h, hkv, d, page, s_max=4, max_pages=8, seed=3)
    got = ragged_paged_attention(
        q, kc, vc, tables, cu, q_lens, seq_lens, n,
        use_pallas=use_pallas)
    want = _oracle(q, cu, q_lens, seq_lens, dense, h, d)
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-5,
                               atol=2e-5)


@pytest.mark.parametrize("use_pallas", [False, True])
def test_empty_and_padded_seq_rows(use_pallas):
    """num_seqs < metadata width, plus an explicit zero-length sequence
    row in the middle: both contribute nothing and corrupt nothing."""
    h, hkv, d, page = 4, 2, 32, 4
    (q, kc, vc, tables, cu, q_lens, seq_lens, n, dense) = _pack(
        [(12, 4), (6, 1)], h, hkv, d, page, s_max=5, max_pages=6, seed=11)
    # splice a zero-length "sequence" between the two real ones
    cu = np.asarray(cu).copy()
    q_lens = np.asarray(q_lens).copy()
    seq_lens = np.asarray(seq_lens).copy()
    cu2 = np.array([cu[0], cu[1], cu[1], cu[2], cu[2], cu[2]], np.int32)
    ql2 = np.array([q_lens[0], 0, q_lens[1], 0, 0], np.int32)
    sl2 = np.array([seq_lens[0], 0, seq_lens[1], 0, 0], np.int32)
    tb2 = np.asarray(tables).copy()
    tb2[2] = tb2[1]
    tb2[1] = 0
    got = ragged_paged_attention(
        q, kc, vc, jnp.asarray(tb2), jnp.asarray(cu2),
        jnp.asarray(ql2), jnp.asarray(sl2), 3, use_pallas=use_pallas)
    want = _oracle(q, cu, q_lens, seq_lens, dense, h, d)
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-5,
                               atol=2e-5)


def test_kernel_matches_ref_exactly_shaped():
    """Kernel (interpret) vs the XLA ref on the same inputs — the pair
    the engine's auto-dispatch switches between."""
    h, hkv, d, page = 4, 2, 32, 4
    (q, kc, vc, tables, cu, q_lens, seq_lens, n, _) = _pack(
        CASES["mixed"], h, hkv, d, page, s_max=6, max_pages=12, seed=42)
    kern = ragged_paged_attention(
        q, kc, vc, tables, cu, q_lens, seq_lens, n, use_pallas=True)
    ref = ragged_paged_attention_ref(
        q, kc, vc, tables, cu, q_lens, seq_lens, n)
    np.testing.assert_allclose(np.asarray(kern), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_decode_row_equals_paged_attention():
    """A 1-token ragged row reproduces the decode entry's semantics
    (same cache, same tables)."""
    from vllm_omni_tpu.ops import paged_attention_ref

    h, hkv, d, page = 4, 2, 32, 4
    (q, kc, vc, tables, cu, q_lens, seq_lens, n, _) = _pack(
        CASES["decode_only"], h, hkv, d, page, s_max=4, max_pages=6,
        seed=7)
    got = ragged_paged_attention(
        q, kc, vc, tables, cu, q_lens, seq_lens, n, use_pallas=True)
    q_rows = jnp.stack([q[int(cu[i])] for i in range(n)])  # [B, H, D]
    want = paged_attention_ref(
        q_rows, kc, vc, tables[:n], seq_lens[:n])
    for i in range(n):
        np.testing.assert_allclose(
            np.asarray(got)[int(cu[i])], np.asarray(want)[i],
            rtol=2e-5, atol=2e-5)


def test_num_seqs_zero():
    h, hkv, d, page = 4, 2, 32, 4
    (q, kc, vc, tables, cu, q_lens, seq_lens, _, _) = _pack(
        [(8, 4)], h, hkv, d, page, s_max=3, max_pages=4, seed=1)
    # an empty batch is all zeros on both paths (every block is a
    # padding block and padding blocks are zeroed)
    got = ragged_paged_attention_ref(
        q, kc, vc, tables, cu, q_lens, seq_lens, 0)
    assert np.all(np.asarray(got) == 0.0)
    kern = ragged_paged_attention(
        q, kc, vc, tables, cu, q_lens, seq_lens, 0, use_pallas=True)
    assert kern.shape == q.shape
    assert np.all(np.asarray(kern) == 0.0)


def test_wide_q_tokens_hand_made_schedule():
    """Three decode rows, then a 70-token chunk, packed in that order
    at 8-token alignment, under 32-token q blocks:

        rows   0..23   three decode sub-blocks
        rows  24..31   the chunk's first sub-block   -> block 0: four
                                                        sequences, narrow
        rows  32..63   chunk tokens  8..39           -> block 1: wide
        rows  64..95   chunk tokens 40..69 and 2 pad -> block 2: wide

    so 62 of the chunk's 70 tokens ride wide tiles, and no decode row."""
    assert wide_q_tokens([1, 1, 1, 70], 32) == 62
    # a q block as narrow as the alignment: every block has one owner
    assert wide_q_tokens([1, 1, 1, 70], 8) == 73
    # decode rows alone share their blocks; a lone row has its own
    assert wide_q_tokens([1] * 8, 32) == 0
    assert wide_q_tokens([1], 32) == 1
    # zero-length metadata rows hold no sub-block
    assert wide_q_tokens([0, 40, 0], 32) == 40
    assert wide_q_tokens([], 32) == 0


@pytest.mark.parametrize("q_lens", [[1, 1, 1, 70], [5, 3], [40], [1] * 9,
                                    [9, 1, 33, 1]])
def test_wide_q_tokens_is_the_kernels_lookup(q_lens):
    """The host count and the in-jit classification the kernel runs on
    agree: tokens of sub-blocks whose q block has a wide owner."""
    q_block, s_max = 32, 12
    cu = np.zeros(s_max + 1, np.int32)
    ql = np.zeros(s_max, np.int32)
    total = 0
    for i, n in enumerate(q_lens):
        cu[i], ql[i] = total, n
        total += align_to_block(n, TB)
    cu[len(q_lens):] = total
    nbw = -(-max(total, 1) // q_block)
    nsub = q_block // TB
    wide_seq, sub_seq = _block_owners(
        jnp.asarray(cu), jnp.asarray(ql), len(q_lens), nbw, nsub, TB)
    wide_seq, sub_seq = np.asarray(wide_seq), np.asarray(sub_seq)
    tokens = 0
    for b, i in enumerate(sub_seq):
        if i >= 0 and wide_seq[b // nsub] >= 0:
            assert wide_seq[b // nsub] == i
            tokens += min(TB, ql[i] - (b * TB - cu[i]))
    assert tokens == wide_q_tokens(q_lens, q_block)


# ------------------------------------------------ the shared (latent) layout
def _latent_pack(specs, heads=20, width=640, used=576, page=16, s_max=4,
                 seed=0):
    """A token-packed batch over ONE pool whose rows are key and value at
    once ([``used`` numbers | zeros] of ``width`` lanes), one kv head."""
    rng = np.random.default_rng(seed)
    pages_per = [-(-c // page) for c, _ in specs]
    pool = np.zeros((1, 1 + sum(pages_per), page, width), np.float32)
    pool[0, 1:, :, :used] = rng.standard_normal(pool[0, 1:, :, :used].shape)
    tables = np.zeros((s_max, max(pages_per) + 1), np.int32)
    cu = np.zeros(s_max + 1, np.int32)
    q_lens, seq_lens = np.zeros(s_max, np.int32), np.zeros(s_max, np.int32)
    total, nxt = 0, 1
    for i, ((ctx, qn), n) in enumerate(zip(specs, pages_per)):
        tables[i, :n] = np.arange(nxt, nxt + n)
        nxt += n
        cu[i], q_lens[i], seq_lens[i] = total, qn, ctx
        total += align_to_block(qn, TB)
    cu[len(specs):] = total
    q = np.zeros((align_to_block(total, TB), heads, width), np.float32)
    for i, (_, qn) in enumerate(specs):
        q[cu[i]:cu[i] + qn, :, :used] = rng.standard_normal(
            (qn, heads, used))
    return (jnp.asarray(q), jnp.asarray(pool), jnp.asarray(tables),
            jnp.asarray(cu), jnp.asarray(q_lens), jnp.asarray(seq_lens),
            len(specs))


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 3e-5),
                                       (jnp.bfloat16, 3e-2)])
def test_shared_pool_kernel_matches_the_reference(dtype, tol):
    """The ragged kernel over a latent pool at the published shape (640
    lanes of which the first 512 are the value, 20 query heads on one kv
    head): two decode rows, a chunk over a cached prefix that crosses q
    blocks and a page boundary, and a fresh short prompt, against the
    reference and, for the chunk's last token, the plain definition."""
    specs = [(45, 1), (130, 1), (100, 37), (9, 9)]
    q, pool, tables, cu, q_lens, seq_lens, n = _latent_pack(specs)
    q, pool = q.astype(dtype), pool.astype(dtype)
    args = (q, pool, None, tables, cu, q_lens, seq_lens, n)
    ref = ragged_paged_attention_ref(*args, scale=1 / 16, v_dim=512)
    got = ragged_paged_attention(*args, scale=1 / 16, use_pallas=True,
                                 v_dim=512)
    assert got.shape == ref.shape == (q.shape[0], 20, 512)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(ref, np.float32), atol=tol)
    row = int(cu[2]) + 36  # position 99 of the third sequence
    keys = np.asarray(pool, np.float32)[0, np.asarray(tables[2])].reshape(
        -1, 640)[:100]
    s = np.asarray(q, np.float32)[row] @ keys.T / 16
    p = np.exp(s - s.max(-1, keepdims=True))
    want = (p / p.sum(-1, keepdims=True)) @ keys[:, :512]
    np.testing.assert_allclose(np.asarray(ref, np.float32)[row], want,
                               atol=tol)
    # padding rows own their output too
    assert not np.asarray(got, np.float32)[int(cu[0]) + 1:int(cu[1])].any()


def test_shared_pool_decode_row_equals_the_decode_kernel():
    """A decode row as a mixed step packs it (one token in an 8-row
    segment) and through the decode entry (one token a segment): one
    pool, one kernel, two layouts, the same numbers."""
    from vllm_omni_tpu.ops import paged_attention

    specs = [(61, 1), (17, 1)]
    q, pool, tables, cu, q_lens, seq_lens, n = _latent_pack(specs, seed=4)
    ragged = ragged_paged_attention(q, pool, None, tables, cu, q_lens,
                                    seq_lens, n, scale=1 / 16,
                                    use_pallas=True, v_dim=512)
    rows = np.asarray(cu[:2])
    decode = paged_attention(q[rows], pool, None, tables[:2], seq_lens[:2],
                             scale=1 / 16, use_pallas=True, v_dim=512)
    np.testing.assert_allclose(np.asarray(ragged)[rows],
                               np.asarray(decode), atol=3e-5)


def test_auto_blocks_count_one_buffer_for_a_shared_pool():
    """At the latent shape (group 20, 640 lanes) the picker takes 16
    tokens (320 rows) over 512 keys, with one KV buffer counted."""
    from vllm_omni_tpu.ops.autotune import auto_ragged_blocks

    assert auto_ragged_blocks(head_dim=640, page_size=16, group=20,
                              kv_itemsize=2, q_itemsize=2,
                              kv_pools=1) == (16, 32, 2)


# ------------------------------------- a window and a selection (ISSUE 34)
def _plain(q_row, keys, values, allowed, scale):
    """The definition, for one packed row: q_row [H, D], keys [L, D]."""
    s = np.where(allowed[None, :], q_row @ keys.T * scale, -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    return (p / p.sum(-1, keepdims=True)) @ values


def _positions(cu, q_lens, seq_lens, n, t):
    """Global position of each packed row (-1 for padding rows)."""
    pos = np.full(t, -1)
    for i in range(n):
        for j in range(int(q_lens[i])):
            pos[int(cu[i]) + j] = int(seq_lens[i]) - int(q_lens[i]) + j
    return pos


def _random_keep(pos, reach, seed=0):
    """A selection as the indexer gives one: a random third of the keys
    at or below each row's position, the row's own key among them."""
    rng = np.random.default_rng(seed)
    cols = np.arange(reach)[None, :]
    keep = (rng.random((len(pos), reach)) < 0.3) | (cols == pos[:, None])
    return keep & (cols <= pos[:, None])


#: the layouts the window and the selection are served over, tiny tiles
#: (pages_per_block 2 = 32 keys a KV block, so a 21-key window and a
#: 200-token context cross several): per-head K and V at GQA, the latent
#: pool of the full layers (640 lanes, value the first 512) and of the
#: sliding ones (1 152 lanes, value the first 1 024)
_LAYOUTS = {
    "gqa": dict(latent=None, h=8, hkv=2, d=128),
    "latent640": dict(latent=dict(heads=16, width=640, used=576), v_dim=512),
    "latent1152": dict(latent=dict(heads=8, width=1152, used=1088),
                       v_dim=1024),
}
_MIXED = [(70, 1), (5, 1), (200, 40), (19, 19)]


def _layout_batch(layout, specs, seed=0):
    """(q, k_cache, v_cache, tables, cu, q_lens, seq_lens, n, kwargs)."""
    if layout["latent"] is None:
        q, kc, vc, tables, cu, q_lens, seq_lens, n, _ = _pack(
            specs, layout["h"], layout["hkv"], layout["d"], 16, 4, 14,
            seed=seed)
        return q, kc, vc, tables, cu, q_lens, seq_lens, n, {"scale": 0.1}
    q, pool, tables, cu, q_lens, seq_lens, n = _latent_pack(
        specs, **layout["latent"], seed=seed)
    return (q, pool, None, tables, cu, q_lens, seq_lens, n,
            {"scale": 1 / 16, "v_dim": layout["v_dim"]})


@pytest.mark.parametrize("bound", ["window", "keep"])
@pytest.mark.parametrize("name", sorted(_LAYOUTS))
def test_a_bounded_mixed_step_matches_the_reference_and_the_definition(
        name, bound):
    """A mixed step (two decode rows, a 40-token chunk ending a 200-token
    context, a fresh prompt) under ``window=21`` or under a random
    selection: the kernel against the reference on every row, and the
    reference against the plain definition on the chunk's last row."""
    q, kc, vc, tables, cu, q_lens, seq_lens, n, kw = _layout_batch(
        _LAYOUTS[name], _MIXED)
    pos = _positions(cu, q_lens, seq_lens, n, q.shape[0])
    reach = tables.shape[1] * 16
    if bound == "window":
        kw["window"] = 21
        allowed = (np.arange(reach)[None, :] <= pos[:, None]) & (
            np.arange(reach)[None, :] > pos[:, None] - 21)
    else:
        allowed = _random_keep(pos, reach)
        kw["keep"] = jnp.asarray(allowed)
    args = (q, kc, vc, tables, cu, q_lens, seq_lens, n)
    ref = ragged_paged_attention_ref(*args, **kw)
    got = ragged_paged_attention(*args, use_pallas=True, pages_per_block=2,
                                 q_block=16, **kw)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=3e-5)
    row = int(cu[2]) + 39
    if vc is None:
        keys = np.asarray(kc)[0, np.asarray(tables[2])].reshape(
            reach, -1)
        want = _plain(np.asarray(q)[row], keys, keys[:, :kw["v_dim"]],
                      allowed[row], kw["scale"])
        np.testing.assert_allclose(np.asarray(ref)[row], want, atol=3e-5)
    assert not np.asarray(got)[int(cu[0]) + 1:int(cu[1])].any()


@pytest.mark.parametrize("bound", ["window", "keep"])
@pytest.mark.parametrize("name", sorted(_LAYOUTS))
def test_a_bounded_decode_row_through_the_decode_entry(name, bound):
    """The same bounds through ``paged_attention`` (one token a segment),
    against ``paged_attention_ref`` and against the mixed step's packing
    of the same rows; a padding row of context 0 among them."""
    from vllm_omni_tpu.ops import paged_attention, paged_attention_ref

    q, kc, vc, tables, cu, q_lens, seq_lens, n, kw = _layout_batch(
        _LAYOUTS[name], [(70, 1), (5, 1), (130, 1)], seed=3)
    rows = np.asarray(cu[:3])
    reach = tables.shape[1] * 16
    pos = np.asarray(seq_lens[:3]) - 1
    if bound == "window":
        kw["window"] = 21
        packed = dict(kw)
    else:
        keep = _random_keep(pos, reach, seed=5)
        kw["keep"] = jnp.asarray(keep)
        full = np.zeros((q.shape[0], reach), bool)
        full[rows] = keep
        packed = dict(kw, keep=jnp.asarray(full))
    ctx = jnp.concatenate([seq_lens[:3], jnp.zeros((1,), jnp.int32)])
    qd = jnp.concatenate([q[rows], q[rows[:1]]])
    td = jnp.concatenate([tables[:3], tables[:1]])
    if "keep" in kw:
        kw["keep"] = jnp.concatenate(
            [kw["keep"], jnp.zeros((1, reach), bool)])
    got = paged_attention(qd, kc, vc, td, ctx, use_pallas=True, **kw)
    ref = paged_attention_ref(qd, kc, vc, td, ctx, **kw)
    np.testing.assert_allclose(np.asarray(got)[:3], np.asarray(ref)[:3],
                               atol=3e-5)
    assert not np.asarray(got)[3].any()
    mixed = ragged_paged_attention(q, kc, vc, tables, cu, q_lens, seq_lens,
                                   n, use_pallas=True, **packed)
    np.testing.assert_allclose(np.asarray(mixed)[rows],
                               np.asarray(got)[:3], atol=3e-5)


def test_a_window_walks_only_the_blocks_it_reaches():
    """Keys below the window may lie in pages that were given back: the
    table then points at page 0, and whatever finite rows that page holds
    (here 1e3 everywhere) reach no row, in the kernel or in the
    reference."""
    layout = _LAYOUTS["latent1152"]
    q, pool, _, tables, cu, q_lens, seq_lens, n, kw = _layout_batch(
        layout, [(200, 1), (200, 40)], seed=7)
    kw["window"] = 21
    want = ragged_paged_attention(q, pool, None, tables, cu, q_lens,
                                  seq_lens, n, use_pallas=True,
                                  pages_per_block=2, **kw)
    # the pages wholly below position 200 - 40 - 21 + 1 go back
    freed = (200 - 40 - 21 + 1) // 16
    tables = tables.at[:, :freed].set(0)
    pool = pool.at[0, 0].set(1e3)
    for use_pallas in (True, False):
        got = ragged_paged_attention(q, pool, None, tables, cu, q_lens,
                                     seq_lens, n, use_pallas=use_pallas,
                                     pages_per_block=2, **kw)
        assert np.isfinite(np.asarray(got)).all()
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=3e-5)


def test_no_window_and_no_selection_trace_to_the_kernel_that_was():
    """``window=None`` and ``keep=None`` leave the kernel's program as it
    was: the same jaxpr as a call that never names them, one fewer operand
    and semaphore than a call with a selection."""
    import jax

    q, pool, _, tables, cu, q_lens, seq_lens, n, kw = _layout_batch(
        _LAYOUTS["latent640"], _MIXED)

    def call(**extra):
        return str(jax.make_jaxpr(lambda q, pool: ragged_paged_attention(
            q, pool, None, tables, cu, q_lens, seq_lens, n, use_pallas=True,
            **kw, **extra))(q, pool))

    plain = call()
    assert call(window=None, keep=None) == plain
    keep = jnp.ones((q.shape[0], tables.shape[1] * 16), bool)
    assert call(keep=keep) != plain and call(window=21) != plain
    assert "has_keep" not in plain and "window" not in plain


# ------------------------------- every kv head in one grid cell (ISSUE 49)
# The grid lost its kv-head axis: a KV block comes in once, by one strided
# copy a page, and the tile attends every kv head against it in a loop.
_HEAD_VARIANTS = ["two_pools", "int8", "window", "keep"]


def _quantize(pool):
    """``[Hkv, P, page, D]`` float -> (int8 data, ``[Hkv, P]`` scales):
    a scale a (kv head, page), so a head that read another's scales
    would show (the heads' ranges differ fivefold)."""
    x = np.asarray(pool) * (1 + 4 * np.arange(pool.shape[0])
                            / max(pool.shape[0] - 1, 1))[:, None, None, None]
    scale = np.maximum(np.abs(x).max(axis=(2, 3)), 1e-6) / 127
    return (jnp.asarray(np.round(x / scale[..., None, None]), jnp.int8),
            jnp.asarray(scale, jnp.float32))


def _head_step(hkv, variant, group=2):
    """The step of ``test_mixed_step_with_a_padding_sub_block_between``
    (decode rows whose tiles hand on within a q block and across two, a
    sub-block nobody owns, a chunk with one wide tile) at ``hkv`` kv
    heads: (args, kwargs, rows of the hole)."""
    specs = [(9, 1), (30, 1), (1, 1), (17, 1), (25, 1), (60, 37)]
    h, d, page = hkv * group, 32, 4
    q, kc, vc, tables, cu, q_lens, seq_lens, n, _ = _pack(
        specs, h, hkv, d, page, s_max=8, max_pages=16, seed=5 + hkv)
    lo = int(cu[len(specs) - 1])
    q = jnp.concatenate([q[:lo], jnp.zeros((TB, h, d), q.dtype), q[lo:]])
    cu = cu.at[len(specs) - 1:].add(TB)
    kw = dict(q_block=16, pages_per_block=2)
    if variant == "int8":
        kc, vc = _quantize(kc), _quantize(vc)
    elif variant == "window":
        kw["window"] = 11
    elif variant == "keep":
        pos = _positions(cu, q_lens, seq_lens, n, q.shape[0])
        kw["keep"] = jnp.asarray(_random_keep(pos, tables.shape[1] * page))
    return (q, kc, vc, tables, cu, q_lens, seq_lens, n), kw, (lo, lo + TB)


def _one_head(cache, head):
    if isinstance(cache, tuple):
        return tuple(x[head: head + 1] for x in cache)
    return cache[head: head + 1]


@pytest.mark.parametrize("variant", _HEAD_VARIANTS)
@pytest.mark.parametrize("hkv", [1, 2, 4, 8])
def test_a_mixed_step_at_every_head_count(hkv, variant):
    """One algorithm at 1, 2, 4 and 8 kv heads, over two pools, int8
    pools with a scale a (head, page), a window and a selection: the
    kernel against the reference on every row; the hole stays zero."""
    args, kw, (lo, hi) = _head_step(hkv, variant)
    tile = {k: kw.pop(k) for k in ("q_block", "pages_per_block")}
    got = ragged_paged_attention(*args, use_pallas=True, **tile, **kw)
    ref = ragged_paged_attention_ref(*args, **kw)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=3e-5)
    assert not np.asarray(got)[lo:hi].any()


@pytest.mark.parametrize("variant", _HEAD_VARIANTS)
@pytest.mark.parametrize("hkv", [2, 4, 8])
def test_each_kv_head_of_a_mixed_step_is_that_head_alone(hkv, variant):
    """A head's result is the parent's digit for digit: the parent ran a
    kv head's tiles in grid cells of their own (grid ``(Hkv, NBW)``), which
    is what this kernel does for a pool of ONE kv head (its loop has one
    turn).  So head ``h`` of the whole call equals, exactly, the call on
    ``pool[h]`` and that head's query heads: the same KV blocks in the
    same order, nothing shared between heads but the copies."""
    (q, kc, vc, *meta), kw, _ = _head_step(hkv, variant)
    group = q.shape[1] // hkv
    got = np.asarray(ragged_paged_attention(q, kc, vc, *meta,
                                            use_pallas=True, **kw))
    for head in range(hkv):
        heads = slice(head * group, (head + 1) * group)
        alone = ragged_paged_attention(
            q[:, heads], _one_head(kc, head), _one_head(vc, head), *meta,
            use_pallas=True, **kw)
        np.testing.assert_array_equal(got[:, heads], np.asarray(alone))
