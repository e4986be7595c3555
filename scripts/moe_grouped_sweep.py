"""Times the experts' grouped matmul alone (``ops/grouped_matmul.py``): the
product of expert-sorted rows with each expert's matrix that ``ops/moe.py
_grouped_mlp`` makes twice a layer.  Both forms, bf16 in, under ``jax.jit``,
at every ``(P, router width, E, K, N)`` a cell of the benchmark produces (its
decode buckets and its token buckets, read from ``benchmark/configs/``):

- ``ragged_dot``: ``jax.lax.ragged_dot``, XLA's, what the tree served
  everywhere until PR 48;
- ``kernel_<tm>_<tn>``: ``_few_rows`` at the tiles ``tiles()`` picks for
  the shape (``--tiles`` adds the other windows of rows and blocks of
  weights, which is how the rule in ``tiles()`` was chosen).

Each line says under ``served`` which of the two ``choice()`` gives the
shape.  The routers are balanced unless ``--skew 0.3`` asks for a seeded
unbalanced one (experts without rows, groups of one row and of hundreds):
with ``--served`` that is the builder's check that the kernel is right at
every shape the op serves it at, which the benchmark's own 48-token check
cannot see (PERF.md, Open question 17a).

    chiprun -- python3 -m scripts.moe_grouped_sweep [--tiles] [--only thinker]
    chiprun -- python3 -m scripts.moe_grouped_sweep --skew 0.3 --served

Prints a JSON line a (shape, form): ms a call by the host's clock around a
burst of calls (``--trace``: also the device's own time from a profiler
trace), the time the weights of the touched experts take at the chip's
bandwidth (``roof_ms``), and the largest difference from a float32 product
on the same inputs.  The table goes to
``chiprun_out/moe_grouped_sweep.json``.  ``--tiny`` rehearses it on the CPU.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

TINY = {"tiny_gate_up": (16, 48, 4, 4, 128, 256),
        "tiny_share_down": (16, 64, 16, 2, 256, 128)}


def cell_calls(config: dict) -> dict:
    """name -> (rows of the step, P, router width, E, K, N): the two grouped
    matmuls of a sparse layer at every decode bucket (1 .. ``max_num_seqs``)
    and every token bucket (16 .. ``max_num_batched_tokens``) of a
    configuration file, through its architecture module's own
    ``program_config``."""
    from benchmark import spec
    from vllm_omni_tpu.worker.model_runner import _make_buckets

    cfg = spec.arch_of(config).program_config(config)
    e = cfg.num_experts
    width = cfg.router_experts or e
    inter = cfg.moe_intermediate_size or cfg.intermediate_size
    hidden = cfg.moe_latent_size or cfg.hidden_size
    gated = 1 if cfg.moe_latent_size else 2  # latent experts are ungated
    engine = config["engine"]
    steps = sorted(set(_make_buckets(1, engine["max_num_seqs"]))
                   | set(_make_buckets(16, engine["max_num_batched_tokens"])))
    calls = {}
    for t in steps:
        p = t * cfg.num_experts_per_tok
        calls[f"{t}_up"] = (t, p, width, e, hidden, gated * inter)
        calls[f"{t}_down"] = (t, p, width, e, inter, hidden)
    return calls


def all_calls() -> dict:
    """Every distinct call of the benchmark's one-chip configurations."""
    calls, seen = {}, set()
    for path in sorted((ROOT / "benchmark" / "configs").glob("*.json")):
        config = json.loads(path.read_text())
        if config.get("tensor_parallel_size", 1) != 1:
            continue
        for name, call in cell_calls(config).items():
            if call[1:] not in seen:
                seen.add(call[1:])
                calls[f"{config['name']}_{name}"] = call
    return calls


def build(call, seed, dtype, skew: float = 0.0):
    """(rows, w, group_sizes) of a router's picks without replacement over
    ``width`` experts of which the first ``e`` are held: the held picks'
    rows first, sorted by expert, as ``routed_moe_share`` sorts them.
    ``skew`` > 0 makes the router a seeded, unbalanced one: that share of
    the experts (the first and the last held among them) is never picked
    while it can be helped, and a tenth of the rest is preferred by every
    token, so that groups are empty, of one row and of many."""
    t, p, width, e, k, n = call
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    scores = jax.random.uniform(ks[0], (t, width))
    if skew:
        empty = jax.random.uniform(ks[3], (width,)) < skew
        empty = empty.at[jnp.asarray([0, e - 1])].set(True)
        hot = jax.random.uniform(ks[4], (width,)) < 0.1
        scores = scores + hot * 0.5 - empty * 2.0
    picks = jax.lax.top_k(scores, p // t)[1]
    sizes = jnp.bincount(picks.reshape(-1), length=width)[:e]
    rows = jax.random.normal(ks[1], (p, k), jnp.float32).astype(dtype)
    w = (jax.random.uniform(ks[2], (e, k, n), jnp.float32, -1, 1)
         / k ** 0.5).astype(dtype)
    return rows, w, sizes.astype(jnp.int32)


def reference(rows, w, sizes):
    """The held rows' product in float32, expert by expert."""
    rows, w = np.asarray(rows, np.float32), np.asarray(w, np.float32)
    out = np.zeros((rows.shape[0], w.shape[-1]), np.float32)
    lo = 0
    for i, size in enumerate(np.asarray(sizes)):
        out[lo:lo + size] = rows[lo:lo + size] @ w[i]
        lo += size
    return out, lo


def timed(fn, args, reps: int, trace: bool) -> tuple[dict, jax.Array]:
    """ms a call: the host's clock around a burst of ``reps`` calls that
    waits once, at its end (what the device takes where it is the bound);
    with ``trace`` also the device's own time from a profiler trace."""
    run = jax.jit(fn).lower(*args).compile()
    out = jax.block_until_ready(run(*args))
    bursts = []
    for _ in range(3):
        t0 = time.perf_counter()
        for _ in range(reps):
            out = run(*args)
        jax.block_until_ready(out)
        bursts.append((time.perf_counter() - t0) * 1e3 / reps)
    row = {"ms": min(bursts), "ms_spread": max(bursts) - min(bursts)}
    if trace:
        from scripts.decode_attention_sweep import device_us

        row["device_ms"] = device_us(run, args, 10)[1] / 1e3
    return row, out


def forms(call, itemsize: int, all_tiles: bool, vmem: int) -> dict:
    """form -> function: ``ragged_dot`` and the kernel at the tiles the
    op picks; with ``all_tiles`` the other windows at that block and the
    other blocks at that window.  A kernel that would ask for more than
    ``vmem`` bytes is left out (the op serves under its own, smaller
    ``VMEM_BUDGET``: the sweep looks past it)."""
    from vllm_omni_tpu.ops import grouped_matmul as gm

    _, p, width, _, k, n = call
    tm, tn, _ = gm.tiles(p, width, k, n, itemsize)
    pairs = [(tm, tn)]
    if all_tiles:
        pairs += [(m, tn) for m in (16, 32, 64, 128) if m != tm]
        pairs += [(tm, n // d) for d in (1, 2, 4)
                  if n % (128 * d) == 0 and n // d != tn]
    out = {"ragged_dot": lambda r, w, s: jax.lax.ragged_dot(r, w, s)}
    for m, c in pairs:
        if gm._vmem(max(p, m), k, c, itemsize) <= vmem:
            out[f"kernel_{m}_{c}"] = (
                lambda r, w, s, m=m, c=c: gm._few_rows(r, w, s, tm=m, tn=c))
    return out


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--reps", type=int, default=20)
    ap.add_argument("--seed", type=int, default=48)
    ap.add_argument("--only", default="", help="comma-separated substrings")
    ap.add_argument("--tiles", action="store_true")
    ap.add_argument("--trace", action="store_true")
    ap.add_argument("--vmem-mb", type=int, default=64,
                    help="leave out a kernel that asks for more VMEM")
    ap.add_argument("--max-rows", type=float, default=160.0,
                    help="skip shapes over this many rows an expert")
    ap.add_argument("--steps", default="",
                    help="comma-separated rows of a step: those alone")
    ap.add_argument("--skew", type=float, default=0.0,
                    help="a seeded unbalanced router: this share of the "
                    "experts without rows (see build)")
    ap.add_argument("--served", action="store_true",
                    help="only the shapes the op serves with the kernel")
    ap.add_argument("--tiny", action="store_true")
    ns = ap.parse_args()
    dev = jax.devices()[0]
    on_tpu = dev.platform == "tpu"
    from benchmark import trace
    from vllm_omni_tpu.ops import grouped_matmul as gm

    # the rehearsal has no chip: its roof is the v5e's, for the table's form
    hbm_byte_s = trace.peaks_of(
        dev.device_kind if on_tpu else "TPU v5 lite")["hbm_byte_s"]
    if ns.tiny:
        calls, dtype = TINY, jnp.float32
    else:
        assert on_tpu, "times come from the chip only"
        calls, dtype = all_calls(), jnp.bfloat16
    rows = []
    only = [s for s in ns.only.split(",") if s]
    steps = {int(t) for t in ns.steps.split(",") if t}
    for name, call in calls.items():
        t, p, width, e, k, n = call
        itemsize = jnp.dtype(dtype).itemsize
        if only and not any(s in name for s in only):
            continue
        if p / width > ns.max_rows or (steps and t not in steps):
            continue
        if ns.served and gm.choice(p, width, k, n, itemsize) != "kernel":
            continue
        args = build(call, ns.seed, dtype, ns.skew)
        want, held = reference(*args)
        touched = int(np.count_nonzero(np.asarray(args[2])))
        roof_ms = ((touched * k * n + p * (k + n)) * itemsize
                   / hbm_byte_s * 1e3)
        for form, fn in forms(call, itemsize, ns.tiles,
                              ns.vmem_mb * 2 ** 20).items():
            row = {"shape": name, "t": t, "p": p, "width": width, "e": e,
                   "k": k, "n": n, "rows_an_expert": round(p / width, 2),
                   "touched": touched, "largest_group": int(args[2].max()),
                   "roof_ms": round(roof_ms, 4),
                   "served": gm.choice(p, width, k, n, itemsize),
                   "form": form}
            try:
                took, got = timed(fn, args, ns.reps, ns.trace and on_tpu)
                row.update(took)
                err = np.abs(np.asarray(got, np.float32)[:held] - want[:held])
                row["max_err"] = float(err.max()) if held else 0.0
                # one rounding of a float32 sum to the dtype
                eps = float(jnp.finfo(dtype).eps)
                # rows behind the last group (a share's absent pairs) are
                # zeros from the kernel; ragged_dot leaves them undefined
                behind = np.asarray(got, np.float32)[held:]
                row["ok"] = bool(row["max_err"] <= 2 * eps * max(
                    float(np.abs(want).max()), 1.0)) and (
                    form == "ragged_dot" or not behind.any())
            except Exception as ex:  # a refused form is a finding
                row["refused"] = str(ex).splitlines()[0][:200]
            rows.append(row)
            print(json.dumps(row), flush=True)
    os.makedirs("chiprun_out", exist_ok=True)
    name = (f"moe_grouped_sweep{'_skew' if ns.skew else ''}"
            f"{'_tiny' if ns.tiny else ''}.json")
    with open(os.path.join("chiprun_out", name), "w") as f:
        json.dump({"device": dev.device_kind, "date": time.strftime(
            "%Y-%m-%d", time.gmtime()), "rows": rows}, f, indent=1)
    raise SystemExit(1 if any(not r.get("ok", True) for r in rows) else 0)


if __name__ == "__main__":
    main()
