"""Time the sampler's filter alone on the chip, at the shape of the
benchmark's cells, over the ways of finding a row's top-k / nucleus
thresholds (sample/sampler.py ``CAP`` is fixed from this table; PERF.md
holds it).

    chiprun -- python -m scripts.sampler_select_sweep

Each case is one sampled step's logits, ``[32|64, 152064]`` float32 of
bf16-rounded values (so ties are as common as in a served step), at the
cells' settings: temperature 0.7, ``top_k`` 20, ``top_p`` 0.8.  Timed,
each as a jitted program of its own (median of ``--reps`` calls, each
ended by ``block_until_ready``):

- ``two_sorts``: the filter as it was before PR 29, a sort of the whole
  vocabulary for the top-k mask and another for the nucleus;
- ``one_sort``: one ``jnp.sort`` of the whole vocabulary, what the
  fallback pays;
- ``top_k CAP``: one ``lax.top_k(x, CAP)``, the bounded selection;
- ``two_stage CAP/blocks``: the same CAP values exactly, by a
  ``lax.top_k`` inside each of ``blocks`` column blocks and another over
  the blocks' winners (the way round a ``lax.top_k`` that lowers to a
  sort);
- ``filter CAP``: ``_filtered_scaled`` whole with that CAP (selection,
  thresholds, predicate, mask);
- ``sample CAP`` / ``sample two_sorts``: ``sample_tokens`` whole (the
  filter, the Gumbel draw, both argmaxes), what a step pays under
  ``omni.sampler``.

Every selection is checked against the sorted row, value for value,
and every ``sample`` against the two-sort definition, token for token.
``--tiny`` runs a small version anywhere: a rehearsal of the script,
never a speed.
"""

import argparse
import json
import os
import statistics
import time

import jax
import jax.numpy as jnp
import numpy as np

from vllm_omni_tpu.sample import sampler

CAPS = (32, 64, 128, 256, 512)
BLOCKS = (27, 297)


@jax.jit
def _one_sort(x):
    return jnp.sort(x, axis=-1)[:, ::-1]


@jax.jit
def _two_sorts(scaled, top_k, top_p):
    """The two masks as they were: each sorts the whole vocabulary."""
    vocab = scaled.shape[-1]
    desc = jnp.sort(scaled, axis=-1)[:, ::-1]
    k_eff = jnp.where(top_k <= 0, vocab, jnp.minimum(top_k, vocab))
    thresh = jnp.take_along_axis(desc, (k_eff - 1)[:, None], axis=-1)
    scaled = jnp.where(scaled < thresh, sampler._NEG_INF, scaled)
    desc = jnp.sort(scaled, axis=-1)[:, ::-1]
    probs = jax.nn.softmax(desc, axis=-1)
    keep = (jnp.cumsum(probs, axis=-1) - probs) < top_p[:, None]
    thresh = jnp.min(jnp.where(keep, desc, jnp.inf), axis=-1, keepdims=True)
    return jnp.where(scaled < thresh, sampler._NEG_INF, scaled)


def _two_stage(x, cap, blocks):
    """The ``cap`` largest values of each row, descending, exactly:
    every one of them is among its own block's ``cap`` largest."""
    rows, vocab = x.shape
    width = -(-vocab // blocks)
    x = jnp.pad(x, ((0, 0), (0, width * blocks - vocab)),
                constant_values=-jnp.inf)
    inner = jax.lax.top_k(x.reshape(rows, blocks, width),
                          min(cap, width))[0]
    return jax.lax.top_k(inner.reshape(rows, -1), cap)[0]


@jax.jit
def _sample_two_sorts(scaled, top_k, top_p, keys):
    return jax.vmap(lambda kd, row: jax.random.categorical(
        jax.random.wrap_key_data(kd), row))(
            keys, _two_sorts(scaled, top_k, top_p)).astype(jnp.int32)


def _select(cap):
    return jax.jit(lambda x: jax.lax.top_k(x, cap)[0])


def _staged(cap, blocks):
    return jax.jit(lambda x: _two_stage(x, cap, blocks))


def _at_cap(cap, fn):
    """``fn`` as a program of its own with ``sampler.CAP`` at ``cap``:
    the constant is read while a function is traced, and ``run`` is a
    fresh function, so a fresh trace."""
    def run(*args):
        kept, sampler.CAP = sampler.CAP, cap
        try:
            return fn(*args)
        finally:
            sampler.CAP = kept

    return jax.jit(run)


def _timed(fn, args, reps):
    out = jax.block_until_ready(fn(*args))  # compiles
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append((time.perf_counter() - t0) * 1e3)
    return out, statistics.median(times)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--reps", type=int, default=20)
    ap.add_argument("--seed", type=int, default=29)
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--out", default="chiprun_out/sampler_sweep.json")
    ns = ap.parse_args()
    dev = jax.devices()[0]
    print(f"device: {dev.platform} {dev.device_kind} x{jax.device_count()}")
    if ns.tiny:
        shapes, caps, blocks = [(4, 1000)], (32, 64), (3,)
    else:
        assert dev.platform == "tpu", "times come from the chip only"
        shapes, caps, blocks = [(64, 152064), (32, 152064)], CAPS, BLOCKS
    rows_out = []

    def note(rows, case, ms, ok=True):
        row = {"rows": rows, "case": case, "ms": ms, "ok": bool(ok)}
        rows_out.append(row)
        print(json.dumps(row), flush=True)

    for rows, vocab in shapes:
        key = jax.random.PRNGKey(ns.seed + rows)
        logits = (3.0 * jax.random.normal(key, (rows, vocab), jnp.float32)
                  ).astype(jnp.bfloat16).astype(jnp.float32)
        temp = jnp.full((rows,), 0.7, jnp.float32)
        top_k = jnp.full((rows,), 20, jnp.int32)
        top_p = jnp.full((rows,), 0.8, jnp.float32)
        keys = jax.vmap(lambda i: jax.random.key_data(
            jax.random.PRNGKey(i)))(jnp.arange(rows, dtype=jnp.uint32))
        scaled = logits / temp[:, None]

        desc, ms = _timed(_one_sort, (scaled,), ns.reps)
        note(rows, "one_sort", ms)
        desc = np.asarray(desc)
        want, ms = _timed(_two_sorts, (scaled, top_k, top_p), ns.reps)
        note(rows, "two_sorts", ms)
        want_tok, ms = _timed(_sample_two_sorts,
                              (scaled, top_k, top_p, keys), ns.reps)
        note(rows, "sample two_sorts", ms)
        for cap in caps:
            got, ms = _timed(_select(cap), (scaled,), ns.reps)
            note(rows, f"top_k {cap}", ms,
                 np.array_equal(np.asarray(got), desc[:, :cap]))
            for nb in blocks:
                got, ms = _timed(_staged(cap, nb), (scaled,), ns.reps)
                note(rows, f"two_stage {cap}/{nb}", ms,
                     np.array_equal(np.asarray(got), desc[:, :cap]))
            (got, path), ms = _timed(
                _at_cap(cap, sampler._filtered_scaled),
                (logits, temp, top_k, top_p), ns.reps)
            note(rows, f"filter {cap}", ms,
                 int(path) == sampler.PATH_BOUNDED
                 and np.array_equal(np.asarray(got) > -1e29,
                                    np.asarray(want) > -1e29))
            (tok, _), ms = _timed(
                _at_cap(cap, sampler.sample_tokens.__wrapped__),
                (logits, temp, top_k, top_p, keys), ns.reps)
            note(rows, f"sample {cap}", ms,
                 np.array_equal(np.asarray(tok), np.asarray(want_tok)))

    os.makedirs(os.path.dirname(ns.out) or ".", exist_ok=True)
    with open(ns.out, "w") as f:
        json.dump({"device": dev.device_kind, "platform": dev.platform,
                   "rows": rows_out}, f, indent=1)
    print(f"\n{'rows':>4} {'case':<22} {'ms':>9}")
    for r in rows_out:
        print(f"{r['rows']:>4} {r['case']:<22} {r['ms']:9.3f}"
              + ("" if r["ok"] else "  WRONG"))
    raise SystemExit(0 if all(r["ok"] for r in rows_out) else 1)


if __name__ == "__main__":
    main()
