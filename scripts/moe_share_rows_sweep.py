"""Times an expert share's grouped MLP alone at Nemotron-3-Super's widths
(128 held experts of a 512-wide router, top-22, ungated relu^2 in a
1024-wide latent): what the rows of ABSENT experts cost.

    chiprun -- python3 -m scripts.moe_share_rows_sweep [--tiny]

``ops/moe.py routed_moe_share`` keeps the pair count at the static T x k:
the pairs of experts that live elsewhere (three in four here) sort behind
the last held expert's group, where the group sizes stop short of them.
For a decode step (128 tokens, 2 816 pairs, about 704 held) and a mixed
step (2 048 tokens, 45 056 pairs, about 11 264 held) this times
``_grouped_mlp`` (a) as the share runs it, over all T x k rows, and (b)
over the held rows alone (the same group sizes on a prefix of the rows):
(a) - (b) is what the absent rows cost.  Prints a JSON line a case:
milliseconds a call by the host's clock around ``--iters`` calls that end
in ``block_until_ready``.  ``--tiny`` rehearses it on the CPU.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--tiny", action="store_true")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from vllm_omni_tpu.ops import moe
    from vllm_omni_tpu.ops.activation import relu2

    held, width, k = (4, 16, 3) if args.tiny else (128, 512, 22)
    latent, inter = (16, 24) if args.tiny else (1024, 2688)
    dtype = jnp.float32 if args.tiny else jnp.bfloat16
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    up = jax.random.normal(ks[0], (held, latent, inter), dtype) * 0.03
    down = jax.random.normal(ks[1], (held, inter, latent), dtype) * 0.02
    rng = np.random.default_rng(0)
    mlp = jax.jit(lambda xs, s: moe._grouped_mlp(xs, up, down, s, relu2))
    for tokens in ((8, 64) if args.tiny else (128, 2048)):
        pairs = tokens * k
        # each pair's expert uniform over the router's width: those under
        # ``held`` are here, sorted in front as the share sorts them
        picks = rng.integers(0, width, pairs)
        sizes = jnp.asarray(np.bincount(picks[picks < held],
                                        minlength=held), jnp.int32)
        here = int(sizes.sum())
        rows = jax.random.normal(ks[2], (pairs, latent), dtype)
        out = {"tokens": tokens, "pairs": pairs, "held_pairs": here}
        for name, xs in (("all_rows_ms", rows), ("held_rows_ms",
                                                 rows[:max(here, 8)])):
            jax.block_until_ready(mlp(xs, sizes))
            t0 = time.monotonic()
            for _ in range(args.iters):
                y = mlp(xs, sizes)
            jax.block_until_ready(y)
            out[name] = (time.monotonic() - t0) * 1e3 / args.iters
        out["device"] = jax.devices()[0].device_kind
        print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
