"""What the benchmark's reference check reads for a model with recurrent
layers when its recurrent state is kept in another precision between
steps: the control that says what the check's limit can see (ISSUE 28).

    chiprun -- python3 -m scripts.state_precision_control \
        --workload qwen3next_longdoc_sat --seed 2800000301 --state bfloat16

The cell's configuration is served as ``benchmark/run.py`` serves it
(weights from the seed, ``build_server``), without warm-up: only the
executables the check's prompt and its decoded tokens reach are compiled.
``--state float32`` is the served precision; anything else casts the
runner's recurrent-state slots before the first step, which is all the
switch there is (``_write_state`` rounds to the cache's dtype at every
chunk's end).  Prints one JSON line with the check's result.
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
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--state", default="float32")
    ap.add_argument("--depth", type=int, default=0,
                    help="serve this many layers instead of the file's")
    ap.add_argument("--allow-cpu", action="store_true")
    args = ap.parse_args()

    from benchmark import run, serve, spec

    cell = spec.resolve_cell(args.workload)
    config = cell["config"]
    if args.depth:
        config = {**config, "num_hidden_layers": args.depth}
    run.prepare_jax()
    device = run.device_gate(cell["cell"]["chips"], args.allow_cpu)

    import jax.numpy as jnp

    from vllm_omni_tpu.config.stage import StageConfig
    from vllm_omni_tpu.entrypoints.openai.api_server import build_server

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
        runner = engine.runner
        if not runner.state_caches:
            raise SystemExit("the cell's model has no recurrent state")
        dtype = jnp.dtype(args.state)
        runner.state_caches = [(conv, rec.astype(dtype))
                               for conv, rec in runner.state_caches]
        check = serve.reference_check(state, engine, config, args.seed)
    finally:
        state.shutdown()
        server.server_close()
    print(json.dumps({"workload": args.workload, "seed": args.seed,
                      "state": str(dtype), "device": device,
                      "depth": config["num_hidden_layers"],
                      "ok": check["ok"],
                      "max_abs_diff": check.get("max_abs_diff"),
                      "tolerance": check.get("tolerance"),
                      "served": check.get("served"),
                      "reference": check.get("reference")}), flush=True)


if __name__ == "__main__":
    main()
