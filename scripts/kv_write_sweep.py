"""Time one step's KV writes alone on the chip, at the pools of the
benchmark's cells, over the ways of putting a step's rows into the paged
pool (ops/paged_attention.py ``write_kv_cache`` is chosen from this
table; PERF.md holds it).

    chiprun -- python -m scripts.kv_write_sweep

A case is one step's writes: ``layers`` x (K, V) pools ``[Hkv, P, 16, D]``
bf16, donated to one jitted program that writes the same ``[T, Hkv, D]``
rows into each at ``slot_mapping`` and returns them, as a served step
does.  Timed by the host's clock around ``block_until_ready`` (median of
``--reps`` calls).  The candidates:

- ``flat_heads``: the write as it was before PR 31, a scatter on the pool
  viewed ``[Hkv, P*16, D]`` with the head as the scatter's window (XLA
  copies the whole pool into the scatter's layout and back);
- ``row_scatter``: ``_scatter_rows``, the head folded into the index,
  ``Hkv*T`` rows of ``[D]`` scattered on ``[Hkv*P*16, D]`` (what serves
  off the TPU and the int8 pool's rows; head-major order and indexing the
  4-D pool by (head, page, offset) timed the same within a tenth and left
  the script);
- ``pages_kernel``: ``_write_pages``, the Pallas page write;
- ``served``: ``write_kv_cache`` as the tree has it (on the chip the row
  scatter up to 256 rows a half, the page write above, at every head
  width).

Each is checked against a row-by-row numpy reference on a small pool,
and its compiled text is searched for an operation of the pool's size
other than the scatter itself.  ``--tiny`` runs a small version anywhere:
a rehearsal of the script, never a speed.
"""

import argparse
import json
import os
import re
import statistics
import time

import jax
import jax.numpy as jnp
import numpy as np

from vllm_omni_tpu.ops.paged_attention import (
    _scatter_rows,
    _write_pages,
    write_kv_cache,
)

PAGE = 16


def _flat_heads(cache, new, slots):
    hkv, p, ps, d = cache.shape
    x = jnp.moveaxis(new, 1, 0).astype(cache.dtype)
    s = jnp.where(slots < 0, p * ps, slots)
    flat = cache.reshape(hkv, p * ps, d).at[:, s].set(x, mode="drop")
    return flat.reshape(cache.shape)


def _pairwise(one):
    return lambda kc, vc, kn, vn, slots: (one(kc, kn, slots),
                                          one(vc, vn, slots))


CANDIDATES = {
    "flat_heads": _pairwise(_flat_heads),
    "row_scatter": _pairwise(_scatter_rows),
    "pages_kernel": _write_pages,
    "served": write_kv_cache,
}


def reference(cache, new, slots):
    """Row by row, the contract: slot = page * 16 + offset, -1 dropped."""
    out = np.array(cache)
    for t, s in enumerate(np.asarray(slots)):
        if s >= 0:
            out[:, s // PAGE, s % PAGE] = np.asarray(new)[t]
    return out


def step_slots(rng, pages, prefill, decodes, t_pad):
    """One served step's slot mapping: a chunk of ``prefill`` tokens that
    starts mid-page, ``decodes`` rows one page each, -1 to the bucket."""
    perm = rng.permutation(pages)
    n_chunk = -(-(prefill + 5) // PAGE)
    chunk = (perm[:n_chunk, None] * PAGE + np.arange(PAGE)).reshape(-1)
    rows = perm[n_chunk:n_chunk + decodes] * PAGE + rng.integers(
        0, PAGE, decodes)
    slots = np.concatenate([chunk[5:5 + prefill], rows])
    return np.concatenate(
        [slots, np.full(t_pad - len(slots), -1)]).astype(np.int32)


def check(name, fn, rng):
    hkv, pages, d, t = 4, 24, 128, 48
    slots = step_slots(rng, pages, 21, 9, t)
    slots[[3, 17]] = -1  # dropped rows among real ones
    kc, vc, kn, vn = (
        jnp.asarray(rng.standard_normal(s), jnp.bfloat16)
        for s in [(hkv, pages, PAGE, d)] * 2 + [(t, hkv, d)] * 2)
    want = reference(kc, kn, slots), reference(vc, vn, slots)
    got = jax.jit(fn)(kc, vc, kn, vn, jnp.asarray(slots))
    ok = all(np.array_equal(np.asarray(g), w) for g, w in zip(got, want))
    if not ok:
        raise SystemExit(f"{name}: differs from the row-by-row reference")


def pool_sized_ops(text, pool_elems):
    """Operations of the compiled text whose result is as large as one
    pool, other than parameters, views and the scatter itself."""
    found = []
    for m in re.finditer(
            r"= \w+\[([\d,]+)\]\S* ([\w\-]+)\(", text):
        n = int(np.prod([int(x) for x in m.group(1).split(",")]))
        if n >= pool_elems and m.group(2) not in (
                "parameter", "bitcast", "get-tuple-element", "tuple",
                "scatter", "fusion"):
            found.append(f"{m.group(2)}[{m.group(1)}]")
    return sorted(set(found))


def time_case(name, fn, layers, hkv, pages, d, slots, reps, rng):
    def step(caches, kn, vn, s):
        return [fn(kc, vc, kn, vn, s) for kc, vc in caches]

    shape = (hkv, pages, PAGE, d)
    make = jax.jit(lambda: [(jnp.zeros(shape, jnp.bfloat16),
                             jnp.zeros(shape, jnp.bfloat16))
                            for _ in range(layers)])
    caches = make()
    t = len(slots)
    kn = jnp.asarray(rng.standard_normal((t, hkv, d)), jnp.bfloat16)
    vn = jnp.asarray(rng.standard_normal((t, hkv, d)), jnp.bfloat16)
    s = jnp.asarray(slots)
    jitted = jax.jit(step, donate_argnums=(0,))
    compiled = jitted.lower(caches, kn, vn, s).compile()
    big = pool_sized_ops(compiled.as_text(), int(np.prod(shape)))
    for _ in range(3):
        caches = compiled(caches, kn, vn, s)
    jax.block_until_ready(caches)
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        caches = compiled(caches, kn, vn, s)
        jax.block_until_ready(caches)
        times.append((time.perf_counter() - t0) * 1e3)
    # the rows are where they belong after all those steps
    sample = np.flatnonzero(slots >= 0)[:: max(1, t // 7)]
    k_last = np.asarray(caches[-1][0][:, slots[sample] // PAGE,
                                      slots[sample] % PAGE])
    if not np.array_equal(k_last, np.moveaxis(np.asarray(kn)[sample], 1, 0)):
        raise SystemExit(f"{name}: rows not found where they were written")
    del caches
    return statistics.median(times), min(times), big


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--reps", type=int, default=20)
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--only", nargs="*", default=None)
    ap.add_argument("--out", default="chiprun_out/kv_write_sweep.json")
    args = ap.parse_args()
    rng = np.random.default_rng(31)
    dev = jax.devices()[0]
    print(f"device: {dev.platform} {dev.device_kind} x{jax.device_count()}")
    names = args.only or list(CANDIDATES)
    for name in names:
        check(name, CANDIDATES[name], rng)
    print("every candidate equals the row-by-row reference")
    if args.tiny:
        pools = {"tiny": (2, 2, 32, 128)}
        steps = {"decode8": (0, 8, 8), "chunk40": (40, 3, 64)}
    else:
        # layers, kv heads, pages, head width of the cells' pools
        pools = {"thinker": (8, 4, 8192, 128),
                 "qwen3next": (2, 2, 32768, 256),
                 "thinker_tp4_shard": (8, 1, 8192, 128)}
        # prefill tokens, decode rows, the bucket padded to
        steps = {"decode64": (0, 64, 64),
                 "mixed512": (300, 60, 512),
                 "chunk2048": (1985, 63, 2048),
                 "doc4096": (2037, 11, 4096)}
    rows = []
    for pool, (layers, hkv, pages, d) in pools.items():
        for step, (prefill, decodes, t_pad) in steps.items():
            slots = step_slots(rng, pages, prefill, decodes, t_pad)
            for name in names:
                med, best, big = time_case(
                    name, CANDIDATES[name], layers, hkv, pages, d, slots,
                    args.reps, rng)
                rows.append(dict(pool=pool, step=step, candidate=name,
                                 ms_median=med, ms_min=best,
                                 pool_sized_ops=big))
                print(f"{pool:18s} {step:10s} {name:14s} "
                      f"{med:8.3f} ms (min {best:.3f})  {big}", flush=True)
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(dict(device=dev.device_kind, reps=args.reps, rows=rows),
                  f, indent=1)


if __name__ == "__main__":
    main()
