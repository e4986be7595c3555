"""Time learned sparse attention's parts alone on the chip, at
dots3-note-prev's widths (128 latent heads over a 640-lane row, an indexer
of 64 heads x 128 keeping 2 048, sliding layers of 64 heads over a
1 152-lane row and a window of 513): which form serves each kind of row
(PERF.md holds the table; ``transformer._paged_attend`` serves what it
keeps).

    chiprun -- python -m scripts.sparse_attention_sweep

At a context of 4k / 8k / 16k tokens, one full layer's:

- ``index_*``: the indexer's scores of 24 decode rows, and of a 2 048-token
  chunk ending there (``ops.indexer``), then the selection by counting
  passes (``topk_keep``) and by ``lax.top_k``;
- ``decode_dense``: 24 decode rows over their whole context (the dense
  walk of PR 33, no selection: what GLM-4.7-Flash's rows cost at 128
  heads); ``decode_keep``: the same walk with the selection as the
  kernel's ``keep`` operand (a): what the tree serves;
  ``decode_gather`` (b): the 2 048 selected rows gathered out of the pool
  (24 x 2 048 x 1 280 B) and attended densely by XLA;
- ``chunk_dense`` / ``chunk_keep``: the 2 048-token chunk absorbed,
  without and with the selection; ``chunk_expanded``: the chunk through
  per-head keys and values of the whole context and the dense flash kernel
  (no selection: the 128-head reading ROADMAP R9 (d) asks for);
- ``window_decode`` / ``window_chunk``: a sliding layer's attention with
  ``window=513``, against ``window_off_*`` (the same rows over the whole
  context).

Timed by the host's clock around ``block_until_ready`` (median of
``--reps`` calls); ``decode_gather`` and ``decode_keep`` are checked
against each other.  ``--tiny`` runs a small version anywhere: a rehearsal
of the script, never a speed.
"""

import argparse
import json
import os
import statistics
import time

import jax
import jax.numpy as jnp
import numpy as np

from vllm_omni_tpu.models.common.transformer import _join_lanes
from vllm_omni_tpu.ops import (
    flash_attention,
    paged_attention,
    ragged_paged_attention,
)
from vllm_omni_tpu.ops import indexer as ix

PAGE = 16


def timed(fn, args, reps: int) -> float:
    jax.block_until_ready(fn(*args))
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append(time.perf_counter() - t0)
    return statistics.median(times) * 1e3


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--tiny", action="store_true")
    args = ap.parse_args()
    if args.tiny:
        heads, nope, rope, v, rank = 8, 64, 64, 64, 128
        s_heads, s_rank = 8, 192
        n_i, d_i, top_k, window = 2, 128, 32, 21
        rows, chunk, contexts, dtype = 3, 32, (64, 128), jnp.float32
    else:
        heads, nope, rope, v, rank = 128, 128, 64, 128, 512
        s_heads, s_rank = 64, 1024
        n_i, d_i, top_k, window = 64, 128, 2048, 513
        rows, chunk, contexts, dtype = 24, 2048, (4096, 8192, 16384), \
            jnp.bfloat16
    width = -(-(rank + rope) // 128) * 128
    s_width = -(-(s_rank + rope) // 128) * 128
    scale, s_scale = (nope + rope) ** -0.5, (s_rank // 4 + rope) ** -0.5
    rs = np.random.default_rng(0)
    max_pages = max(contexts) // PAGE
    pages = rows * max_pages + 1

    def pool_of(w, used):
        p = np.zeros((1, pages, PAGE, w), np.float32)
        p[..., :used] = rs.standard_normal((1, pages, PAGE, used)) * 0.5
        return jnp.asarray(p, dtype)

    pool, s_pool = pool_of(width, rank + rope), pool_of(s_width, s_rank + rope)
    i_pool = pool_of(d_i, d_i)
    w_kvb = jnp.asarray(rs.uniform(-1, 1, (rank, heads, nope + v))
                        * rank ** -0.5, dtype)
    tables = jnp.asarray(
        1 + rs.permutation(pages - 1).reshape(rows, max_pages), jnp.int32)
    out = {"device": jax.devices()[0].device_kind, "cases": []}

    def gather_attend(q, idx, tables, pool):
        """(b): q [B, H, W], idx [B, K] positions -> [B, H, rank]."""
        slots = jnp.take_along_axis(tables, idx // PAGE, 1) * PAGE \
            + idx % PAGE
        sel = pool.reshape(-1, pool.shape[-1])[slots]      # [B, K, W]
        s = jnp.einsum("bhd,bkd->bhk", q, sel,
                       preferred_element_type=jnp.float32) * scale
        p = jax.nn.softmax(s, -1).astype(sel.dtype)
        return jnp.einsum("bhk,bkv->bhv", p, sel[..., :rank])

    def expanded(q, w_kvb, pool, tables, ctx):
        t = q.shape[0]
        lat = pool[0, tables[0, :ctx // PAGE]].reshape(ctx, width)
        kv = jnp.einsum("sr,rhd->shd", lat[:, :rank], w_kvb)
        k_rope = jnp.broadcast_to(lat[:, None, rank:rank + rope],
                                  (ctx, heads, rope))
        k = _join_lanes([kv[..., :nope], k_rope], nope + rope)
        # the dense kernel takes one head width: the value padded to the
        # key's with zeros, the output cut back
        val = _join_lanes([kv[..., nope:]], nope + rope)
        return flash_attention(
            q[None], k[None], val[None], causal=True, scale=scale,
            block_q=min(t, 512), block_k=min(ctx, 512),
            q_offsets=jnp.asarray([ctx - t], jnp.int32))[0][..., :v]

    # one wrapper each, made once: a context's length is a shape
    score_d = jax.jit(ix.indexer_scores_decode)
    keep_fn = jax.jit(lambda s: ix.topk_keep(s, top_k))
    topk_fn = jax.jit(lambda s: jax.lax.top_k(s, top_k)[1])
    attend = jax.jit(lambda q, p, t, l, k=None: paged_attention(
        q, p, None, t, l, scale=scale, v_dim=rank, keep=k))
    gather = jax.jit(gather_attend)
    score_c = jax.jit(lambda q, w, p, t, cu, ql, sl: ix.indexer_scores(
        q, w, p, t, cu, ql, sl, 1))
    ragged = jax.jit(lambda q, p, t, cu, ql, sl, k=None:
                     ragged_paged_attention(
                         q, p, None, t, cu, ql, sl, 1, scale=scale,
                         v_dim=rank, keep=k))
    exp = jax.jit(expanded, static_argnames="ctx")
    slide_d = jax.jit(lambda q, p, t, l, win: paged_attention(
        q, p, None, t, l, scale=s_scale, v_dim=s_rank, window=win),
        static_argnames="win")
    slide_c = jax.jit(lambda q, p, t, cu, ql, sl, win:
                      ragged_paged_attention(
                          q, p, None, t, cu, ql, sl, 1, scale=s_scale,
                          v_dim=s_rank, window=win), static_argnames="win")
    for ctx in contexts:
        case = {"context": ctx, "rows": rows, "chunk": chunk}
        lens = jnp.full((rows,), ctx, jnp.int32)
        t_d = tables[:, :ctx // PAGE]
        # ---- the indexer and the selection, decode rows
        q_i = jnp.asarray(rs.standard_normal((rows, n_i, d_i)), dtype)
        w_i = jnp.asarray(rs.standard_normal((rows, n_i)), jnp.float32)
        case["index_scores_decode_ms"] = timed(
            score_d, (q_i, w_i, i_pool, t_d, lens), args.reps)
        sc = score_d(q_i, w_i, i_pool, t_d, lens)
        case["select_count_decode_ms"] = timed(keep_fn, (sc,), args.reps)
        case["select_topk_decode_ms"] = timed(topk_fn, (sc,), args.reps)
        keep, idx = keep_fn(sc), topk_fn(sc)
        # ---- decode rows: dense walk, (a) keep, (b) gather
        q = jnp.asarray(rs.standard_normal((rows, heads, width)) * 0.3, dtype)
        case["decode_dense_ms"] = timed(attend, (q, pool, t_d, lens),
                                        args.reps)
        case["decode_keep_ms"] = timed(attend, (q, pool, t_d, lens, keep),
                                       args.reps)
        case["decode_gather_ms"] = timed(gather, (q, idx, t_d, pool),
                                         args.reps)
        a = np.asarray(attend(q, pool, t_d, lens, keep), np.float32)
        b = np.asarray(gather(q, idx, t_d, pool), np.float32)
        case["keep_vs_gather_max_abs_diff"] = float(np.abs(a - b).max())
        # ---- the chunk ending at ctx (sequence 0)
        meta = (tables[:1, :ctx // PAGE], jnp.asarray([0, chunk], jnp.int32),
                jnp.asarray([chunk], jnp.int32),
                jnp.asarray([ctx], jnp.int32))
        q_ic = jnp.asarray(rs.standard_normal((chunk, n_i, d_i)), dtype)
        w_ic = jnp.asarray(rs.standard_normal((chunk, n_i)), jnp.float32)
        case["index_scores_chunk_ms"] = timed(
            score_c, (q_ic, w_ic, i_pool, *meta), args.reps)
        sc_c = score_c(q_ic, w_ic, i_pool, *meta)
        case["select_count_chunk_ms"] = timed(keep_fn, (sc_c,), args.reps)
        case["select_topk_chunk_ms"] = timed(topk_fn, (sc_c,), args.reps)
        keep_c = keep_fn(sc_c)
        qc = jnp.asarray(rs.standard_normal((chunk, heads, width)) * 0.3,
                         dtype)
        case["chunk_dense_ms"] = timed(ragged, (qc, pool, *meta), args.reps)
        case["chunk_keep_ms"] = timed(ragged, (qc, pool, *meta, keep_c),
                                      args.reps)
        q_e = jnp.asarray(rs.standard_normal((chunk, heads, nope + rope))
                          * 0.3, dtype)
        case["chunk_expanded_ms"] = timed(
            lambda *a: exp(*a, ctx=ctx), (q_e, w_kvb, pool, meta[0]),
            args.reps)
        # ---- a sliding layer: the window against the whole context
        qs = jnp.asarray(rs.standard_normal((rows, s_heads, s_width)) * 0.3,
                         dtype)
        qsc = jnp.asarray(rs.standard_normal((chunk, s_heads, s_width)) * 0.3,
                          dtype)
        for name, win in (("window", window), ("window_off", None)):
            case[f"{name}_decode_ms"] = timed(
                lambda *a: slide_d(*a, win=win), (qs, s_pool, t_d, lens),
                args.reps)
            case[f"{name}_chunk_ms"] = timed(
                lambda *a: slide_c(*a, win=win), (qsc, s_pool, *meta),
                args.reps)
        out["cases"].append(case)
        print(json.dumps(case), flush=True)
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/sparse_attention_sweep.json", "w") as f:
        json.dump(out, f, indent=1)


if __name__ == "__main__":
    main()
