"""Times the state-space rule's recurrent kernel alone (``ops/ssd.py
_recurrent_inplace``: one token a row, the pool of slots updated where it
lies) at granite-4.0-h-small's widths over its ``head_block``, and checks
each against the gather form on the same inputs.

    chiprun -- python3 -m scripts.ssd_kernel_sweep [--rows 128] [--tiny]

A layer's call at 128 rows reads and writes 128 x 4.19 MB: 1.07 GB, 1.31 ms
at a v5e's 819 GB/s.  Prints a JSON line a block: milliseconds a call (the
host's clock around ``--iters`` donated calls that end in
``block_until_ready``), the GB/s that is, and the largest difference from
the gather form.  ``--tiny`` rehearses it on the CPU (interpret mode).
"""

from __future__ import annotations

import argparse
import functools
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rows", type=int, default=128)
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--blocks", type=int, nargs="*",
                    default=[8, 16, 32, 64, 128])
    ap.add_argument("--tiny", action="store_true")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    from vllm_omni_tpu.ops import ssd

    h, p, n = (8, 8, 128) if args.tiny else (128, 64, 128)
    rows = 6 if args.tiny else args.rows
    slots = rows
    ks = jax.random.split(jax.random.PRNGKey(0), 7)
    u = jax.random.normal(ks[0], (rows, h, p))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (rows, h)) - 2.0)
    a = -dt * jnp.exp(jax.random.uniform(ks[2], (h,), maxval=2.7))
    b = jax.random.normal(ks[3], (rows, n)) * n ** -0.5
    c = jax.random.normal(ks[4], (rows, n))
    perm = jax.random.permutation(ks[5], slots)[:rows].astype(jnp.int32)
    # a padding row in the middle and one at the end, a fresh sequence
    live = jnp.ones((rows,), bool).at[rows // 2].set(False).at[-1].set(False)
    at = jnp.where(live, perm, slots)
    fresh = jnp.zeros((rows,), bool).at[1].set(True)

    def pool():
        return jax.random.normal(ks[6], (slots, h, p, n))

    want_y, want_pool = jax.jit(ssd._recurrent_gather)(
        u, dt, a, b, c, pool(), at, fresh, live)
    moved = 2.0 * int(live.sum()) * h * p * n * 4
    run = jax.jit(
        lambda pl, hb: ssd._recurrent_inplace(
            u, dt, a, b, c, pl, at, fresh, live, head_block=hb),
        static_argnums=(1,), donate_argnums=(0,))
    for hb in args.blocks:
        if hb > h:
            continue
        step = functools.partial(run, hb=hb)
        y, got = step(pool())
        err = max(float(jnp.abs(y - want_y).max()),
                  float(jnp.abs(got - want_pool).max()))
        jax.block_until_ready(got)
        t0 = time.perf_counter()
        for _ in range(args.iters):
            y, got = step(got)
        jax.block_until_ready((y, got))
        ms = (time.perf_counter() - t0) * 1e3 / args.iters
        print(json.dumps({
            "head_block": hb, "rows": rows, "live": int(live.sum()),
            "ms_a_call": ms, "gb_s": moved / ms / 1e6, "max_abs_err": err,
            "device": jax.devices()[0].device_kind}), flush=True)


if __name__ == "__main__":
    main()
