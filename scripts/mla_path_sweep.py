"""Time latent attention's two forms alone on the chip, at GLM-4.7-Flash's
widths over a latent paged pool (models/common/transformer.py
``_mla_mixer`` serves the one this table keeps; PERF.md holds it).

    chiprun -- python -m scripts.mla_path_sweep

A prefill case is one layer's attention for one 2048-token chunk that ends
a context of 2k / 8k / 16k tokens, from the chunk's ``[q_nope | q_rope]``
(20 heads) and the pool ``[1, P, 16, 640]`` bf16 that already holds the
context's latent rows, to the per-head output ``[T, 20, 256]``:

- ``absorbed``: what the tree serves.  ``q_lat = q_nope W_UK^T``, the
  ragged paged kernel over the pool itself (one kv head, key 640 lanes,
  value its first 512, 20 query heads), ``o = o_lat W_UV``;
- ``expanded``: the context's rows gathered out of their pages, every
  row expanded through ``kv_b`` to per-head ``[k_nope | v]`` (the product
  of the context's size), the shared rotary key laid beside each head's
  ``k_nope``, and the dense flash kernel over them with the chunk's offset.

Timed by the host's clock around ``block_until_ready`` (median of
``--reps`` calls); each form's output is checked against the other's.
``--tiny`` runs a small version anywhere: a rehearsal of the script, never
a speed.  (The decode rows of this model, 24 at 8k of context, are a shape
of ``scripts/decode_attention_sweep.py`` since PR 33, when the decode
kernel this script also timed went.)
"""

import argparse
import functools
import json
import os
import statistics
import time

import jax
import jax.numpy as jnp
import numpy as np

from vllm_omni_tpu.models.common.transformer import _join_lanes
from vllm_omni_tpu.ops import flash_attention, ragged_paged_attention

PAGE = 16
HEADS, NOPE, ROPE, V, RANK, WIDTH = 20, 192, 64, 256, 512, 640
SCALE = (NOPE + ROPE) ** -0.5


def absorbed(q, w_kvb, pool, tables, cu, q_lens, seq_lens):
    """q [T, H, nope + rope] -> [T, H, v] through the ragged kernel."""
    q_lat = jnp.einsum("thn,rhn->thr", q[..., :NOPE], w_kvb[..., :NOPE])
    q_full = _join_lanes([q_lat, q[..., NOPE:]], WIDTH)
    o_lat = ragged_paged_attention(q_full, pool, None, tables, cu, q_lens,
                                   seq_lens, 1, scale=SCALE, v_dim=RANK)
    return jnp.einsum("thr,rhv->thv", o_lat, w_kvb[..., NOPE:])


def expanded(q, w_kvb, pool, tables, cu, q_lens, seq_lens, ctx: int):
    """The same through per-head keys and values of the whole context."""
    t = q.shape[0]
    rows = pool[0, tables[0, :ctx // PAGE]].reshape(ctx, WIDTH)
    kv = jnp.einsum("sr,rhd->shd", rows[:, :RANK], w_kvb)  # [ctx, H, 448]
    k_rope = jnp.broadcast_to(rows[:, None, RANK:RANK + ROPE],
                              (ctx, HEADS, ROPE))
    k = _join_lanes([kv[..., :NOPE], k_rope], NOPE + ROPE)
    # blocks named: the picker's choice for this shape overruns VMEM at
    # head width 256 (my chip run, PR 32)
    o = flash_attention(q[None], k[None], kv[None, ..., NOPE:], causal=True,
                        scale=SCALE, block_q=min(t, 1024),
                        block_k=min(ctx, 512),
                        q_offsets=jnp.asarray([ctx - t], jnp.int32))
    return o[0]


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
    ap.add_argument("--reps", type=int, default=10)
    ap.add_argument("--tiny", action="store_true")
    args = ap.parse_args()
    chunk, contexts = ((64, (64, 256)) if args.tiny
                       else (2048, (2048, 8192, 16384)))
    dtype = jnp.float32 if args.tiny else jnp.bfloat16
    rs = np.random.default_rng(0)
    pages = max(contexts) // PAGE + 1
    pool = np.zeros((1, pages, PAGE, WIDTH), np.float32)
    pool[..., :RANK + ROPE] = rs.standard_normal(
        (1, pages, PAGE, RANK + ROPE)) * 0.5
    pool = jnp.asarray(pool, dtype)
    w_kvb = jnp.asarray(rs.uniform(-1, 1, (RANK, HEADS, NOPE + V))
                        * RANK ** -0.5, dtype)
    out = {"device": jax.devices()[0].device_kind, "cases": []}
    # one wrapper each: the context's length is a shape of ``expanded``
    prefill = {"absorbed": jax.jit(absorbed),
               "expanded": jax.jit(expanded, static_argnames="ctx")}
    for ctx in contexts:
        q = jnp.asarray(rs.standard_normal((chunk, HEADS, NOPE + ROPE)),
                        dtype)
        tables = jnp.asarray(1 + rs.permutation(pages - 1)[:ctx // PAGE],
                             jnp.int32)[None]
        meta = (tables, jnp.asarray([0, chunk], jnp.int32),
                jnp.asarray([chunk], jnp.int32),
                jnp.asarray([ctx], jnp.int32))
        forms = {"absorbed": prefill["absorbed"],
                 "expanded": functools.partial(prefill["expanded"], ctx=ctx)}
        got = {k: np.asarray(f(q, w_kvb, pool, *meta), np.float32)
               for k, f in forms.items()}
        case = {"chunk": chunk, "context": ctx,
                "max_abs_diff": float(np.abs(got["absorbed"]
                                             - got["expanded"]).max()),
                "output_rms": float(np.sqrt((got["expanded"] ** 2).mean()))}
        for k, f in forms.items():
            case[f"{k}_ms"] = timed(f, (q, w_kvb, pool, *meta), args.reps)
        out["cases"].append(case)
        print(json.dumps(case), flush=True)
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/mla_path_sweep.json", "w") as f:
        json.dump(out, f, indent=1)


if __name__ == "__main__":
    main()
