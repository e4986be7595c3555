"""What the seeded routers of the Nemotron-3-Super configuration pick, a
layer at a time, over the reference check's prompt: the held share of a
token's 22 picks, how many of the 128 held experts a 128-token batch
touches, and how much of a router's input is one direction common to every
token (a random stack's outputs carry such a direction, and a router that
reads it sends every token to the same experts).

    chiprun -- python3 -m scripts.nemotron_h_router_probe \
        --workload nemotron3s_assist_sat --seeds 4600000201 4600000206

The weights are the cell's (``arch.init_weights`` from the seed), the
forward the plain float32 reference's own layers
(``benchmark/archs/nemotron_h.py``), over ``reference_check.prompt_tokens``
seeded ids.  Prints one JSON line a seed.  ``--tiny-config FILE`` takes a
configuration file in place of the workload's (a CPU rehearsal).
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", default="nemotron3s_assist_sat")
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--tiny-config")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark import spec
    from benchmark.archs import nemotron_h as arch

    config = (spec.load_json(Path(args.tiny_config)) if args.tiny_config
              else spec.resolve_cell(args.workload)["config"])
    cfg = arch.program_config(config)
    top_k, held = config["num_experts_per_tok"], config["n_routed_experts"]
    offset = config.get("expert_offset", 0)
    eps = config["layer_norm_epsilon"]
    hp = (eps, config["head_dim"],
          (config["mamba_num_heads"], config["mamba_head_dim"],
           config["ssm_state_size"], config["n_groups"]),
          top_k, float(config["routed_scaling_factor"]), offset)
    n = config.get("reference_check", {}).get("prompt_tokens", 40)
    make = jax.jit(lambda key: arch.init_weights(
        key, cfg, jnp.dtype(config["serve_dtype"])))
    for seed in args.seeds:
        params = make(jax.random.PRNGKey(seed % (2 ** 31 - 1)))
        ids = jnp.asarray(np.random.default_rng(seed).integers(
            3, config["vocab_size"], n), jnp.int32)
        layers = []
        with jax.default_matmul_precision("highest"):
            x = params["embed"]["w"][ids].astype(jnp.float32)
            for layer in params["layers"]:
                if "experts" in layer:
                    h = arch._rms(x, layer["post_norm"]["w"].astype(
                        jnp.float32), eps)
                    scores = jax.nn.sigmoid(
                        h @ layer["router"]["w"].astype(jnp.float32))
                    _, picks = jax.lax.top_k(
                        scores + layer["router"]["e_bias"], top_k)
                    here = (picks >= offset) & (picks < offset + held)
                    # distinct held experts a batch of 128 tokens touches
                    touched = [len(set(np.asarray(
                        picks[t0:t0 + 128][here[t0:t0 + 128]]).tolist()))
                        for t0 in range(0, n - 127, 128)]
                    mean = jnp.mean(h, axis=0)
                    layers.append({
                        "held_share_pct": 100 * float(jnp.mean(here)),
                        "touched_of_held": float(np.mean(touched)),
                        "common_direction_pct": 100 * float(
                            jnp.sum(mean * mean)
                            / jnp.mean(jnp.sum(h * h, axis=-1)))})
                x = arch._layer(layer, x, hp)
        print(json.dumps({
            "seed": seed, "tokens": n, "layers": layers,
            "held_share_pct": float(np.mean(
                [la["held_share_pct"] for la in layers])),
            "touched_of_held": float(np.mean(
                [la["touched_of_held"] for la in layers])),
            "device": jax.devices()[0].device_kind}), flush=True)
        del params


if __name__ == "__main__":
    main()
