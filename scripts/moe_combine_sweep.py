"""Times the experts' combine alone: the step that ends ``routed_moe`` and
``routed_moe_share`` (``ops/moe.py``), which turns the grouped matmul's
expert-sorted rows ``y [T*k, H]`` back into one row a token, weighted by
the router.  Its forms, bf16 in, under ``jax.jit``, at every ``(T, k, H)``
a cell of the benchmark runs:

- ``scatter``: the form the tree had until PR 43: ``y * w[order]`` and
  ``zeros((T, H)).at[order // k].add(y)``, summed in ``y``'s dtype;
- ``gather``: ``y[inv].reshape(T, k, H)`` weighted in ``[T, k]`` order and
  summed over k in float32 (``inv`` the inverse of the sort): what
  ``_combine`` serves since PR 43;
- ``kmajor``: the same gather laid out pick-major, ``[k, T, H]``, so the
  sum runs over whole ``[T, H]`` slabs and k pads no tile (faster alone;
  dots3-note-prev's warm-up never returned with it: PERF.md, PR 43);
- ``rowwise``: k row-gathers ``y[inv[:, j]]`` accumulated one by one in
  float32, which never holds a ``[T*k, H]`` intermediate (fastest alone;
  its 8-10 gathers a layer cost 6-7 s of a warm set-up: PERF.md, PR 43);
- ``served``: ``ops/moe.py _combine`` as the tree has it.

and the two ways to ``inv`` (``argsort(order)``; ``zeros.at[order].set(iota)``)
at every ``T*k``.  A share's shapes hold one pick in ``live`` of held experts:
the pairs of absent experts sort behind the held ones and are masked out.

    chiprun -- python3 -m scripts.moe_combine_sweep [--tiny]

Prints a JSON line a (shape, form): the device's own time a call from a
profiler trace (all device operations of the call), the host's clock around
a call, the temporaries the compiled program asks for, and the largest
difference from a float64 sum on the same inputs.  The table goes to
``chiprun_out/moe_combine_sweep.json``.  ``--tiny`` rehearses it on the CPU.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

# cell -> (T, k, H, router width, experts held): held == width is routed_moe
SHAPES = {
    "thinker_2048": (2048, 8, 2048, 128, 128),
    "thinker_1024": (1024, 8, 2048, 128, 128),
    "thinker_64": (64, 8, 2048, 128, 128),
    "qwen3next_2048": (2048, 10, 2048, 512, 128),
    "glm_2048": (2048, 4, 2048, 64, 64),
    "glm_32": (32, 4, 2048, 64, 64),
    "dots3_2048": (2048, 8, 5120, 256, 32),
    "lfm2_4096": (4096, 4, 2048, 32, 32),
    "lfm2_256": (256, 4, 2048, 32, 32),
    "granite_2048": (2048, 10, 4096, 72, 18),
    "granite_128": (128, 10, 4096, 72, 18),
}
TINY = {"tiny": (12, 2, 16, 4, 4), "tiny_k1": (5, 1, 16, 4, 4),
        "tiny_share": (16, 4, 16, 16, 2)}


def build(shape, seed, dtype):
    """(y, order, topk_w, held): a router's picks without replacement, the
    sort ``routed_moe_share`` makes of them, random rows."""
    t, k, h, width, e_held = shape
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    scores = jax.random.uniform(ks[0], (t, width))
    topk_w, idx = jax.lax.top_k(scores, k)
    topk_w = topk_w / topk_w.sum(-1, keepdims=True)
    held = idx < e_held
    order = jnp.argsort(jnp.where(held, idx, e_held).reshape(-1))
    y = jax.random.normal(ks[1], (t * k, h), jnp.float32).astype(dtype)
    return y, order.astype(jnp.int32), topk_w, held


def inv_argsort(order):
    return jnp.argsort(order)


def inv_scatter(order):
    return jnp.zeros_like(order).at[order].set(
        jnp.arange(order.shape[0], dtype=order.dtype))


def scatter(y, order, topk_w, held):
    t, k = topk_w.shape
    w = topk_w.reshape(-1)[order]
    y = jnp.where(held.reshape(-1)[order][:, None],
                  y * w[:, None].astype(y.dtype), 0)
    return jnp.zeros((t, y.shape[1]), y.dtype).at[order // k].add(y)


def gather(y, order, topk_w, held):
    t, k = topk_w.shape
    rows = jnp.where(held[:, :, None],
                     y[inv_argsort(order)].reshape(t, k, -1), 0)
    return jnp.sum(rows.astype(jnp.float32) * topk_w[:, :, None],
                   axis=1).astype(y.dtype)


def kmajor(y, order, topk_w, held):
    t, k = topk_w.shape
    inv = inv_argsort(order).reshape(t, k)
    rows = jnp.where(held.T[:, :, None],
                     y[inv.T.reshape(-1)].reshape(k, t, -1), 0)
    return jnp.sum(rows.astype(jnp.float32) * topk_w.T[:, :, None],
                   axis=0).astype(y.dtype)


def rowwise(y, order, topk_w, held):
    t, k = topk_w.shape
    inv = inv_argsort(order).reshape(t, k)
    acc = jnp.zeros((t, y.shape[1]), jnp.float32)
    for j in range(k):
        row = jnp.where(held[:, j, None], y[inv[:, j]], 0)
        acc = acc + row.astype(jnp.float32) * topk_w[:, j, None]
    return acc.astype(y.dtype)


def served(y, order, topk_w, held):
    from vllm_omni_tpu.ops import moe

    return moe._combine(y, order, topk_w, held).astype(y.dtype)


FORMS = {"scatter": scatter, "gather": gather, "kmajor": kmajor,
         "rowwise": rowwise, "served": served}


def reference(y, order, topk_w, held):
    """The sum in float64, pair by pair in the router's order."""
    t, k = topk_w.shape
    y, order = np.asarray(y, np.float64), np.asarray(order)
    inv = np.empty_like(order)
    inv[order] = np.arange(order.shape[0])
    w = np.where(np.asarray(held), np.asarray(topk_w, np.float64), 0.0)
    return np.einsum("tkh,tk->th", y[inv].reshape(t, k, -1), w)


def timed(fn, args, reps: int, on_tpu: bool) -> tuple[dict, jax.Array]:
    """(times and temporaries of the jitted form, its output)."""
    from scripts.decode_attention_sweep import device_us

    run = jax.jit(fn).lower(*args).compile()
    out = jax.block_until_ready(run(*args))
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(run(*args))
        times.append((time.perf_counter() - t0) * 1e3)
    row = {"host_ms": statistics.median(times),
           "temp_bytes": run.memory_analysis().temp_size_in_bytes}
    if on_tpu:  # all device operations of a call, from a profiler trace
        row["device_ms"] = device_us(run, args, 10)[1] / 1e3
    return row, out


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--reps", type=int, default=20)
    ap.add_argument("--seed", type=int, default=43)
    ap.add_argument("--only", default="")
    ap.add_argument("--tiny", action="store_true")
    ns = ap.parse_args()
    dev = jax.devices()[0]
    on_tpu = dev.platform == "tpu"
    shapes, dtype = (TINY, jnp.float32) if ns.tiny else (SHAPES, jnp.bfloat16)
    if not ns.tiny:
        assert on_tpu, "times come from the chip only"
    rows = []

    def emit(row):
        rows.append(row)
        print(json.dumps(row), flush=True)

    for name, shape in shapes.items():
        if ns.only and ns.only not in name:
            continue
        t, k, h, width, e_held = shape
        args = build(shape, ns.seed, dtype)
        want = reference(*args)
        scale = float(np.abs(want).max())
        for form, fn in FORMS.items():
            row = {"shape": name, "t": t, "k": k, "h": h,
                   "live": f"{e_held}/{width}", "form": form}
            try:
                took, got = timed(fn, args, ns.reps, on_tpu)
                row.update(took)
                row["max_err"] = float(
                    np.abs(np.asarray(got, np.float64) - want).max())
                # a sum of k rounded rows: within a few steps of the dtype
                eps = float(jnp.finfo(dtype).eps)
                row["ok"] = bool(row["max_err"] <= 4 * k * eps * scale)
            except Exception as e:  # a refused form is a finding
                row["refused"] = str(e).splitlines()[0][:200]
            emit(row)
        for form, fn in (("inv_argsort", inv_argsort),
                         ("inv_scatter", inv_scatter)):
            row = {"shape": name, "pairs": t * k, "form": form}
            took, got = timed(fn, args[1:2], ns.reps, on_tpu)
            row.update(took)
            row["ok"] = bool(np.array_equal(
                np.asarray(got)[np.asarray(args[1])], np.arange(t * k)))
            emit(row)
    os.makedirs("chiprun_out", exist_ok=True)
    name = f"moe_combine_sweep{'_tiny' if ns.tiny else ''}.json"
    with open(os.path.join("chiprun_out", name), "w") as f:
        json.dump({"device": dev.device_kind, "date": time.strftime(
            "%Y-%m-%d", time.gmtime()), "rows": rows}, f, indent=1)
    raise SystemExit(1 if any(not r.get("ok", True) for r in rows) else 0)


if __name__ == "__main__":
    main()
