"""The indexer of learned sparse attention (DeepSeek-V3.2's "lightning
indexer"): every cached token keeps one small index key beside its KV row,
a step's token scores the whole of its context with a few light heads,

    I[t, s] = sum_j w[t, j] * relu(q[t, j] . k[s]),      s <= t,

and attends only the ``top_k`` best-scoring keys.  Two ops:

- ``indexer_scores`` — the scores of a token-packed step's rows against
  the index keys of their sequences' paged contexts, in the packed layout
  and under the metadata contract of ``ops/ragged_paged_attention.py``;
  float32, ``-1e30`` at every key a row may not see (above it, past its
  context, padding rows).  On the TPU a Pallas kernel over (8-token
  sub-block, 512-key block): a sub-block belongs to one sequence, so its
  keys are one contiguous slab of the per-sequence gather of the index
  pool (``[S, L, D]``: the pool's rows are 256 bytes, a page 4 KB; at 32
  sequences of 17 664 keys the gather is 145 MB a layer and takes 0.4 ms of
  HBM time).  Elsewhere ``indexer_scores_ref`` (a gather a token).
- ``topk_keep`` — the selection by rank: a row keeps its ``top_k``
  largest scores, ties to the lower position, every visible key where it
  sees at most ``top_k``.  The k-th largest score is found by 32 counting
  passes over the scores' bit patterns (a binary search on the monotone
  unsigned image of a float32), not by a sort of ``[T, L]``.

The result is the ``keep`` operand of the paged-attention ops.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from vllm_omni_tpu.ops._dispatch import auto_use_pallas, interpret_flag
from vllm_omni_tpu.ops.paged_attention import paged_tiling_reason
from vllm_omni_tpu.ops.ragged_paged_attention import (
    DEFAULT_TOKEN_BLOCK,
    _block_owners,
)

_NEG_INF = -1e30

#: keys a grid step of the score kernel takes (one block of the gathered
#: index keys: 512 x 128 bfloat16 = 128 KB)
INDEX_KV_BLOCK = 512
#: rows ``topk_keep`` selects at a time: bounds its temporaries (the
#: scores' unsigned image, the tie ranks) to ``rows x L`` words
_KEEP_ROWS = 256


def indexer_scores_ref(q, w, pool, page_tables, cu_q_lens, q_lens,
                       seq_lens, num_seqs):
    """Pure-XLA reference of ``indexer_scores`` (a gather a token: test
    scale only)."""
    t = q.shape[0]
    page = pool.shape[2]
    s_max = q_lens.shape[0]
    nseq = jnp.asarray(num_seqs, jnp.int32).reshape(())
    rows = jnp.arange(t)
    starts = cu_q_lens[:s_max]
    in_seq = ((rows[None, :] >= starts[:, None])
              & (rows[None, :] < (starts + q_lens)[:, None])
              & (jnp.arange(s_max) < nseq)[:, None])
    seq_of = jnp.argmax(in_seq, axis=0)
    valid = jnp.any(in_seq, axis=0)
    q_pos = seq_lens[seq_of] - q_lens[seq_of] + rows - starts[seq_of]
    reach = page_tables.shape[1] * page
    keys = pool[0][page_tables].reshape(s_max, reach, -1)[seq_of]
    dots = jnp.einsum("thd,tld->thl", q.astype(jnp.float32),
                      keys.astype(jnp.float32),
                      precision=jax.lax.Precision.HIGHEST)
    scores = jnp.einsum("th,thl->tl", w.astype(jnp.float32),
                        jax.nn.relu(dots),
                        precision=jax.lax.Precision.HIGHEST)
    seen = (jnp.arange(reach)[None, :] <= q_pos[:, None]) & valid[:, None]
    return jnp.where(seen, scores, _NEG_INF)


def _scores_kernel(seq_ref, last_ref, pos0_ref, nvalid_ref,  # SMEM
                   q_ref,    # [tb * H, D]: a sub-block's heads, token-major
                   wbd_ref,  # [tb, tb * H] f32: w laid block-diagonally
                   k_ref,    # [kb, D]: a block of the sequence's index keys
                   o_ref,    # [tb, kb] f32
                   *, kb: int):
    del seq_ref, last_ref
    j, blk = pl.program_id(0), pl.program_id(1)
    n, pos0 = nvalid_ref[j], pos0_ref[j]
    tb = o_ref.shape[0]

    @pl.when((n > 0) & (blk * kb <= pos0 + n - 1))
    def _block():
        dots = jax.lax.dot_general(
            q_ref[...], k_ref[...], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        # the heads' weighted sum as one product: row r of wbd holds
        # token r's weights at its own heads' columns, zeros elsewhere
        scores = jnp.dot(wbd_ref[...], jnp.maximum(dots, 0.0),
                         preferred_element_type=jnp.float32,
                         precision=jax.lax.Precision.HIGHEST)
        row = jax.lax.broadcasted_iota(jnp.int32, (tb, kb), 0)
        k_pos = blk * kb + jax.lax.broadcasted_iota(jnp.int32, (tb, kb), 1)
        o_ref[...] = jnp.where((k_pos <= pos0 + row) & (row < n), scores,
                               _NEG_INF)

    @pl.when(jnp.logical_not((n > 0) & (blk * kb <= pos0 + n - 1)))
    def _unseen():
        o_ref[...] = jnp.full(o_ref.shape, _NEG_INF, jnp.float32)


@functools.partial(jax.jit, static_argnames=("token_block", "use_pallas"))
def _indexer_scores(q, w, pool, page_tables, cu_q_lens, q_lens, seq_lens,
                    num_seqs, token_block, use_pallas):
    if not use_pallas:
        return indexer_scores_ref(q, w, pool, page_tables, cu_q_lens,
                                  q_lens, seq_lens, num_seqs)
    t, h, d = q.shape
    tb, kb = token_block, INDEX_KV_BLOCK
    page = pool.shape[2]
    s_max, max_pages = page_tables.shape
    reach = max_pages * page
    padded = -(-reach // kb) * kb
    nt = t // tb
    # each sequence's index keys, contiguous: [S, padded, D]
    keys = jnp.pad(pool[0][page_tables].reshape(s_max, reach, d),
                   ((0, 0), (0, padded - reach), (0, 0)))
    _, sub_seq = _block_owners(cu_q_lens, q_lens, num_seqs, nt, 1, tb)
    seq = jnp.maximum(sub_seq, 0)
    tok0 = jnp.arange(nt, dtype=jnp.int32) * tb - cu_q_lens[seq]
    nvalid = jnp.where(sub_seq >= 0,
                       jnp.clip(q_lens[seq] - tok0, 0, tb), 0)
    pos0 = seq_lens[seq] - q_lens[seq] + tok0
    # the last block a sub-block reads: past it the index map stays put,
    # and a block that does not change is not fetched again
    last = jnp.maximum(pos0 + nvalid - 1, 0) // kb
    eye = jnp.eye(tb, dtype=jnp.float32)
    wbd = (eye[None, :, :, None]
           * w.astype(jnp.float32).reshape(nt, 1, tb, h)
           ).reshape(nt, tb, tb * h)
    out = pl.pallas_call(
        functools.partial(_scores_kernel, kb=kb),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(nt, padded // kb),
            in_specs=[
                pl.BlockSpec((None, tb * h, d), lambda j, b, *_: (j, 0, 0)),
                pl.BlockSpec((None, tb, tb * h), lambda j, b, *_: (j, 0, 0)),
                pl.BlockSpec(
                    (None, kb, d),
                    lambda j, b, seq, last, *_: (
                        seq[j], jnp.minimum(b, last[j]), 0)),
            ],
            out_specs=pl.BlockSpec((None, tb, kb),
                                   lambda j, b, *_: (j, 0, b)),
        ),
        out_shape=jax.ShapeDtypeStruct((nt, tb, padded), jnp.float32),
        interpret=interpret_flag(),
    )(seq.astype(jnp.int32), last.astype(jnp.int32),
      pos0.astype(jnp.int32), nvalid.astype(jnp.int32),
      q.reshape(nt, tb * h, d), wbd, keys)
    return out.reshape(t, padded)[:, :reach]


def indexer_scores(
    q: jax.Array,            # [T, Hi, Di] token-packed index queries
    w: jax.Array,            # [T, Hi] the heads' weights of each token
    pool: jax.Array,         # [1, P, page, Di] the index keys' pool
    page_tables: jax.Array,  # [S, max_pages]
    cu_q_lens: jax.Array,    # [S+1] aligned segment starts
    q_lens: jax.Array,       # [S]
    seq_lens: jax.Array,     # [S] context incl. this step's tokens
    num_seqs,
    token_block: int = DEFAULT_TOKEN_BLOCK,
    use_pallas: Optional[bool] = None,
) -> jax.Array:
    """``[T, max_pages * page]`` float32: packed row ``t``'s score of
    every key of its sequence's context, ``-1e30`` where it may not see
    the key (see the module docstring)."""
    if use_pallas is None:
        reason = paged_tiling_reason(q.shape[-1], pool.shape[2])
        if not reason and q.shape[0] % token_block:
            reason = (f"packed length {q.shape[0]} % token_block "
                      f"{token_block} != 0")
        use_pallas = auto_use_pallas("indexer_scores", reason)
    return _indexer_scores(q, w, pool, page_tables, cu_q_lens, q_lens,
                           seq_lens, jnp.asarray(num_seqs, jnp.int32),
                           token_block, use_pallas)


def indexer_scores_decode(q, w, pool, block_tables, context_lens,
                          use_pallas: Optional[bool] = None):
    """The decode entry: ``[B, Hi, Di]`` queries, one token a sequence
    (at ``context_lens - 1``; a row of context 0 is padding)."""
    b = q.shape[0]
    lens = context_lens.astype(jnp.int32)
    if use_pallas is None:
        use_pallas = auto_use_pallas(
            "indexer_scores", paged_tiling_reason(q.shape[-1],
                                                  pool.shape[2]))
    return _indexer_scores(
        q, w, pool, block_tables, jnp.arange(b + 1, dtype=jnp.int32),
        (lens > 0).astype(jnp.int32), lens, jnp.asarray(b, jnp.int32),
        1, use_pallas)


def _keep_rows(scores, top_k: int):
    """``topk_keep`` of a block of rows."""
    # -0.0 and 0.0 tie as numbers, so they share an image
    scores = jnp.where(scores == 0.0, 0.0, scores)
    bits = jax.lax.bitcast_convert_type(scores, jnp.uint32)
    # the unsigned image that orders as the floats do
    key = jnp.where(bits >> 31 == 1, ~bits, bits | jnp.uint32(1 << 31))

    def narrow(i, lo):
        # the largest threshold that still leaves top_k keys at or over it
        cand = lo | (jnp.uint32(1 << 31) >> i.astype(jnp.uint32))
        enough = jnp.sum(key >= cand[:, None], axis=1) >= top_k
        return jnp.where(enough, cand, lo)

    kth = jax.lax.fori_loop(
        0, 32, narrow, jnp.zeros((scores.shape[0],), jnp.uint32))[:, None]
    over = key > kth
    tied = key == kth
    room = top_k - jnp.sum(over, axis=1, keepdims=True)
    keep = over | (tied & (jnp.cumsum(tied, axis=1) <= room))
    return keep & (scores > 0.5 * _NEG_INF)


@functools.partial(jax.jit, static_argnames=("top_k",))
def topk_keep(scores: jax.Array, top_k: int) -> jax.Array:
    """``[T, L]`` bool: the ``top_k`` largest scores of each row, ties to
    the lower column, and never a column at ``-1e30`` (a row that sees at
    most ``top_k`` keys keeps them all)."""
    t, l = scores.shape
    if l <= top_k:
        return scores > 0.5 * _NEG_INF
    if t <= _KEEP_ROWS or t % _KEEP_ROWS:
        return _keep_rows(scores, top_k)
    return jax.lax.map(
        functools.partial(_keep_rows, top_k=top_k),
        scores.reshape(t // _KEEP_ROWS, _KEEP_ROWS, l)).reshape(t, l)
