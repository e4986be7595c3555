"""The indexer of learned sparse attention (ops/indexer.py): the scores of
a token-packed step against the index keys of its sequences' paged
contexts, and the selection by rank, each against its plain definition."""

import jax.numpy as jnp
import numpy as np
import pytest

from vllm_omni_tpu.ops import indexer as ix
from vllm_omni_tpu.ops.ragged_paged_attention import align_to_block

PAGE = 16


def _batch(specs, heads=4, d=128, s_max=4, max_pages=40, seed=0):
    """A token-packed step over an index pool [1, P, 16, d]: (q, w, pool,
    tables, cu, q_lens, seq_lens, n)."""
    rng = np.random.default_rng(seed)
    pages = 1 + sum(-(-c // PAGE) for c, _ in specs)
    pool = rng.standard_normal((1, pages, PAGE, d)).astype(np.float32)
    tables = np.zeros((s_max, max_pages), np.int32)
    cu = np.zeros(s_max + 1, np.int32)
    q_lens, seq_lens = np.zeros(s_max, np.int32), np.zeros(s_max, np.int32)
    total, nxt = 0, 1
    for i, (ctx, qn) in enumerate(specs):
        n = -(-ctx // PAGE)
        tables[i, :n] = np.arange(nxt, nxt + n)
        nxt += n
        cu[i], q_lens[i], seq_lens[i] = total, qn, ctx
        total += align_to_block(qn, 8)
    cu[len(specs):] = total
    t = align_to_block(total, 16)
    q = rng.standard_normal((t, heads, d)).astype(np.float32)
    w = rng.standard_normal((t, heads)).astype(np.float32)
    return tuple(map(jnp.asarray, (q, w, pool, tables, cu, q_lens,
                                   seq_lens))) + (len(specs),)


def test_scores_of_a_mixed_step_match_the_reference_and_the_definition():
    """Two decode rows, a 40-token chunk ending a 600-token context (two
    512-key blocks) and a fresh prompt: the kernel against the reference
    everywhere, the reference against ``sum_j w_j relu(q_j . k_s)`` on the
    chunk's last row, and -1e30 wherever a row may not look."""
    specs = [(70, 1), (5, 1), (600, 40), (19, 19)]
    args = _batch(specs)
    q, w, pool, tables, cu, q_lens, seq_lens, n = args
    ref = ix.indexer_scores_ref(*args)
    got = ix.indexer_scores(*args, use_pallas=True)
    assert got.shape == ref.shape == (q.shape[0], 40 * PAGE)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=2e-5,
                               rtol=1e-5)
    row = int(cu[2]) + 39  # position 599 of the third sequence
    keys = np.asarray(pool)[0, np.asarray(tables[2])].reshape(-1, 128)[:600]
    want = np.einsum("h,hs->s", np.asarray(w)[row], np.maximum(
        np.asarray(q)[row] @ keys.T, 0))
    np.testing.assert_allclose(np.asarray(ref)[row, :600], want, atol=2e-4)
    assert (np.asarray(got)[row, 600:] == -1e30).all()
    # a chunk's first row sees up to its own position, padding rows nothing
    first = int(cu[2])
    assert (np.asarray(got)[first, 561:] == -1e30).all()
    assert np.asarray(got)[first, 560] > -1e29
    assert (np.asarray(got)[int(cu[0]) + 1:int(cu[1])] == -1e30).all()


def test_scores_of_decode_rows_through_the_decode_entry():
    args = _batch([(70, 1), (600, 1), (5, 1)], seed=2)
    q, w, pool, tables, cu, q_lens, seq_lens, n = args
    rows = np.asarray(cu[:3])
    ctx = jnp.concatenate([seq_lens[:3], jnp.zeros((1,), jnp.int32)])
    qd = jnp.concatenate([q[rows], q[:1]])
    wd = jnp.concatenate([w[rows], w[:1]])
    got = ix.indexer_scores_decode(qd, wd, pool, tables, ctx,
                                   use_pallas=True)
    packed = ix.indexer_scores_ref(*args)
    np.testing.assert_allclose(np.asarray(got)[:3], np.asarray(packed)[rows],
                               atol=2e-5, rtol=1e-5)
    assert (np.asarray(got)[3] == -1e30).all()  # a row of context 0


@pytest.mark.parametrize("rows", [24, 512])
def test_the_selection_is_the_top_k_by_rank_with_ties_to_the_left(rows):
    """Scores rounded to a tenth (many exact ties, signed zeros among
    them), each row seeing ``row % 300 + 1`` columns: the kept set is the
    first ``k`` of the visible columns sorted by (score descending, column
    ascending); 512 rows go through the row-blocked path."""
    rng = np.random.default_rng(1)
    sc = np.round(rng.standard_normal((rows, 300)), 1).astype(np.float32)
    seen = np.arange(300)[None, :] <= (np.arange(rows) % 300)[:, None]
    sc = np.where(seen, sc, -1e30).astype(np.float32)
    keep = np.asarray(ix.topk_keep(jnp.asarray(sc), 16))
    for r in range(rows):
        visible = np.nonzero(seen[r])[0]
        want = sorted(visible, key=lambda c: (-sc[r, c], c))[:16]
        assert set(want) == set(np.nonzero(keep[r])[0]), r


def test_a_context_no_longer_than_k_keeps_what_is_seen():
    sc = jnp.where(jnp.arange(12)[None, :] <= jnp.arange(5)[:, None], 1.0,
                   -1e30)
    keep = np.asarray(ix.topk_keep(sc, 16))
    assert (keep == (np.arange(12)[None, :] <= np.arange(5)[:, None])).all()
