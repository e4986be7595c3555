"""What the benchmark's reference check reads for a model with a latent
cache when its latent rows are written in a lower precision than the
configuration states: the control that says what the check's limit can see
(ISSUE 32).

    chiprun -- python3 -m scripts.mla_precision_control \
        --workload glm47flash_agent_sat --seed 3200000301 --mantissa-bits 3

The cell's configuration is served as ``benchmark/run.py`` serves it
(weights from the seed, ``build_server``), without warm-up: only the
executables the check's prompt and its decoded tokens reach are compiled.
``--mantissa-bits 7`` is the served precision (bfloat16 rows); fewer
rounds every latent row to that many mantissa bits on its way into the
pool (3 are float8 e4m3's, one step below bfloat16), by integer arithmetic
on the bits so that it needs no 8-bit type of the compiler; the pool, the
kernels and the reference stay as they are.  ``--own-share`` draws the
weights with another ``EXPERT_OWN_SHARE`` (1.0: unrelated experts, where a
rounding that swaps a token's marginal pick reads 0.1-0.3).  Prints one
JSON line with the check's result.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))


def round_mantissa(x, bits: int):
    """``x`` rounded to nearest at ``bits`` mantissa bits (the exponent's
    range untouched), in ``x``'s dtype."""
    import jax
    import jax.numpy as jnp

    drop = 23 - bits
    raw = jax.lax.bitcast_convert_type(x.astype(jnp.float32), jnp.uint32)
    raw = (raw + jnp.uint32(1 << (drop - 1))) & jnp.uint32(
        ~((1 << drop) - 1) & 0xFFFFFFFF)
    return jax.lax.bitcast_convert_type(raw, jnp.float32).astype(x.dtype)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--mantissa-bits", type=int, default=7)
    ap.add_argument("--own-share", type=float, default=None,
                    help="draw the weights with this much of a routed "
                         "expert its own (the architecture module's "
                         "EXPERT_OWN_SHARE; 1.0 = unrelated experts)")
    ap.add_argument("--allow-cpu", action="store_true")
    args = ap.parse_args()

    from benchmark import run, serve, spec

    cell = spec.resolve_cell(args.workload)
    config = cell["config"]
    arch = spec.arch_of(config)
    if args.own_share is not None:
        arch.EXPERT_OWN_SHARE = args.own_share
    run.prepare_jax()
    device = run.device_gate(cell["cell"]["chips"], args.allow_cpu)

    from vllm_omni_tpu.config.stage import StageConfig
    from vllm_omni_tpu.entrypoints.openai.api_server import build_server
    from vllm_omni_tpu.models.common import transformer as tfm

    if args.mantissa_bits < 7:
        write = tfm.write_kv_cache

        def rounded_write(k_cache, v_cache, k_new, v_new, slots):
            if v_cache is not None:
                raise SystemExit("the cell's model has no latent cache")
            return write(k_cache, None,
                         round_mantissa(k_new, args.mantissa_bits), None,
                         slots)

        tfm.write_kv_cache = rounded_write

    timings: dict = {}
    stage = StageConfig(
        stage_id=0, stage_type="llm",
        engine_args={
            "model_factory": serve.model_factory,
            "model_factory_args": {"config": config, "seed": args.seed,
                                   "timings": timings},
            "warmup": False, **config["engine"]},
        engine_input_source=[-1], final_output=True,
        final_output_type="text")
    server, state = build_server(model="benchmark", stage_configs=[stage],
                                 host="127.0.0.1", port=0)
    try:
        engine = state.omni._omni.stages[0].engine
        if engine.runner.kv_layout.kind != "latent":
            raise SystemExit("the cell's model has no latent cache")
        check = serve.reference_check(state, engine, config, args.seed)
    finally:
        state.shutdown()
        server.server_close()
    print(json.dumps({"workload": args.workload, "seed": args.seed,
                      "mantissa_bits": args.mantissa_bits,
                      "own_share": arch.EXPERT_OWN_SHARE, "device": device,
                      "ok": check["ok"],
                      "max_abs_diff": check.get("max_abs_diff"),
                      "tolerance": check.get("tolerance"),
                      "served": check.get("served"),
                      "reference": check.get("reference")}), flush=True)


if __name__ == "__main__":
    main()
