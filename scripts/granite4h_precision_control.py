"""What the benchmark's reference check reads for granite-4.0-h-small when
the served path departs from what the configuration states: the control
that says what the check's limit can see (ISSUE 42).

    chiprun -- python3 -m scripts.granite4h_precision_control \
        --workload granite4h_assist_sat --seed 4200000301 --alter state

The cell's configuration is served as ``benchmark/run.py`` serves it
(weights from the seed, ``build_server``), without warm-up: only the
executables the check's prompt and its decoded tokens reach are compiled.
``--alter`` names the departures, none being the served path:

- ``state``: every Mamba-2 layer's slots (the rule's ``[128, 64, 128]``
  state and the convolution's carried rows) are kept in bfloat16 between
  steps, the precision below the float32 the file states (the arithmetic
  stays float32; a bfloat16 pool takes the gather form, not the kernel);
- ``scale``: the attention layer's softmax scale is left at ``head_dim **
  -0.5`` in the program, the reference keeping the published 1/128.

Prints one JSON line with the check's result.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--alter", nargs="*", default=[],
                    choices=("state", "scale"))
    ap.add_argument("--allow-cpu", action="store_true")
    args = ap.parse_args()

    from benchmark import run, serve, spec

    cell = spec.resolve_cell(args.workload)
    config = cell["config"]
    run.prepare_jax()
    device = run.device_gate(cell["cell"]["chips"], args.allow_cpu)

    import jax.numpy as jnp

    from vllm_omni_tpu.config.stage import StageConfig
    from vllm_omni_tpu.entrypoints.openai.api_server import build_server

    def model_factory(**kwargs):
        params, cfg, extra = serve.model_factory(**kwargs)
        if "scale" in args.alter:
            cfg = dataclasses.replace(cfg, attention_multiplier=None)
        return params, cfg, extra

    timings: dict = {}
    stage = StageConfig(
        stage_id=0, stage_type="llm",
        engine_args={
            "model_factory": model_factory,
            "model_factory_args": {"config": config, "seed": args.seed,
                                   "timings": timings},
            "warmup": False, **config["engine"]},
        engine_input_source=[-1], final_output=True,
        final_output_type="text")
    server, state = build_server(model="benchmark", stage_configs=[stage],
                                 host="127.0.0.1", port=0)
    try:
        engine = state.omni._omni.stages[0].engine
        if not engine.runner.state_caches:
            raise SystemExit("the cell's model carries no state")
        if "state" in args.alter:
            engine.runner.state_caches = [
                tuple(a.astype(jnp.bfloat16) for a in layer)
                for layer in engine.runner.state_caches]
        check = serve.reference_check(state, engine, config, args.seed)
    finally:
        state.shutdown()
        server.server_close()
    print(json.dumps({"workload": args.workload, "seed": args.seed,
                      "altered": args.alter, "device": device,
                      "ok": check["ok"],
                      "max_abs_diff": check.get("max_abs_diff"),
                      "tolerance": check.get("tolerance"),
                      "served": check.get("served"),
                      "reference": check.get("reference")}), flush=True)


if __name__ == "__main__":
    main()
