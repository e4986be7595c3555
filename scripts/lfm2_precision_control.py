"""What the benchmark's reference check reads for LFM2-8B-A1B when what a
sequence carries is kept in a lower precision than the configuration
states: the control that says what the check's limit can see (ISSUE 39).

    chiprun -- python3 -m scripts.lfm2_precision_control \
        --workload lfm2_assist_sat --seed 3900000301 --round pages state

The cell's configuration is served as ``benchmark/run.py`` serves it
(weights from the seed, ``build_server``), without warm-up: only the
executables the check's prompt and its decoded tokens reach are compiled.
``--round`` names what is rounded to ``--mantissa-bits`` (3 are float8
e4m3's, one step below bfloat16) on its way into the cache, by integer
arithmetic on the bits (``mla_precision_control.round_mantissa``):
``pages`` the K and V rows of the three attention layers, ``state`` the
convolutions' carried rows (float32 in the configuration; ``--mantissa-
bits 7`` keeps them in bfloat16's precision); nothing named is the served
path.  ``--own-share`` draws the weights with another share of a routed
expert its own (1.0: unrelated experts).  Prints one JSON line with the
check's result.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))

from scripts.mla_precision_control import round_mantissa  # noqa: E402


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--round", nargs="*", default=[],
                    choices=("pages", "state"))
    ap.add_argument("--mantissa-bits", type=int, default=3)
    ap.add_argument("--own-share", type=float, default=None)
    ap.add_argument("--allow-cpu", action="store_true")
    args = ap.parse_args()

    from benchmark import run, serve, spec
    from benchmark.archs import glm4_moe_lite

    cell = spec.resolve_cell(args.workload)
    config = cell["config"]
    if args.own_share is not None:
        glm4_moe_lite.EXPERT_OWN_SHARE = args.own_share
    run.prepare_jax()
    device = run.device_gate(cell["cell"]["chips"], args.allow_cpu)

    from vllm_omni_tpu.config.stage import StageConfig
    from vllm_omni_tpu.entrypoints.openai.api_server import build_server
    from vllm_omni_tpu.models.common import transformer as tfm

    bits = args.mantissa_bits
    if "pages" in args.round:
        write = tfm.write_kv_cache
        tfm.write_kv_cache = lambda k_cache, v_cache, k, v, slots: write(
            k_cache, v_cache, round_mantissa(k, bits),
            round_mantissa(v, bits), slots)
    if "state" in args.round:
        write_state = tfm._write_state
        tfm._write_state = lambda cache, slots, state, scope: write_state(
            cache, slots, [round_mantissa(a, bits) for a in state], scope)

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
        if not engine.runner.state_caches:
            raise SystemExit("the cell's model carries no state")
        check = serve.reference_check(state, engine, config, args.seed)
    finally:
        state.shutdown()
        server.server_close()
    print(json.dumps({"workload": args.workload, "seed": args.seed,
                      "rounded": args.round, "mantissa_bits": bits,
                      "own_share": glm4_moe_lite.EXPERT_OWN_SHARE,
                      "device": device, "ok": check["ok"],
                      "max_abs_diff": check.get("max_abs_diff"),
                      "tolerance": check.get("tolerance"),
                      "served": check.get("served"),
                      "reference": check.get("reference")}), flush=True)


if __name__ == "__main__":
    main()
