"""What the benchmark's reference check reads for a model with learned
sparse attention and sliding-window layers when one of the things it is
there to see is taken out of the SERVED path: the controls that say what
the check's limit can see (ISSUE 34).

    chiprun -- python3 -m scripts.sparse_window_control \
        --workload dots3note_agent_sat --seed 3400000101 --off selection

The cell's configuration is served as ``benchmark/run.py`` serves it
(weights from the seed, ``build_server``), without warm-up: only the
executables the check's prompt and its decoded tokens reach are compiled.
``--off none`` is the served program; ``selection`` makes the full layers
attend every visible key (the indexer still runs and scores; its selection
is replaced by "everything it saw"); ``window`` makes the sliding layers
attend their whole context (the kernels are not told the window, and no
window page is given back); ``precision`` gives paged attention its
operands (the queries, every cached row, and the index keys) with float8's
three bits of mantissa where the configuration states bfloat16's seven
(the nearest precision below it).  Each is done
HERE, on the program's functions, and is no option of the program.  The
reference stays as it is.  Prints one JSON line with the check's result.
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
    ap.add_argument("--off", default="none",
                    choices=("none", "selection", "window", "precision"))
    ap.add_argument("--allow-cpu", action="store_true")
    args = ap.parse_args()

    from benchmark import run, serve, spec

    cell = spec.resolve_cell(args.workload)
    config = cell["config"]
    run.prepare_jax()
    device = run.device_gate(cell["cell"]["chips"], args.allow_cpu)

    from vllm_omni_tpu.config.stage import StageConfig
    from vllm_omni_tpu.core.kv_cache_manager import KVCacheManager
    from vllm_omni_tpu.entrypoints.openai.api_server import build_server
    import jax
    import jax.numpy as jnp

    from vllm_omni_tpu.models.common import transformer as tfm

    if args.off == "selection":
        tfm.indexer_ops.topk_keep = lambda scores, top_k: scores > -1e29
    elif args.off == "window":
        for name in ("paged_attention", "ragged_paged_attention"):
            op = getattr(tfm, name)
            setattr(tfm, name, lambda *a, _op=op, **kw: _op(
                *a, **{k: v for k, v in kw.items() if k != "window"}))
        KVCacheManager._slid_out = lambda self, request: 0
    elif args.off == "precision":
        write = tfm.write_kv_cache

        def float8_mantissa(x):
            # bfloat16 [sign | 8 exponent | 7 mantissa] rounded to the
            # nearest value with 3 bits of mantissa (float8 e4m3's)
            if x is None:
                return None
            bits = jax.lax.bitcast_convert_type(
                x.astype(jnp.bfloat16), jnp.uint16)
            bits = (bits + jnp.uint16(8)) & jnp.uint16(0xFFF0)
            return jax.lax.bitcast_convert_type(
                bits, jnp.bfloat16).astype(x.dtype)

        tfm.write_kv_cache = lambda k_cache, v_cache, k, v, slots: write(
            k_cache, v_cache, float8_mantissa(k), float8_mantissa(v), slots)
        for name in ("paged_attention", "ragged_paged_attention"):
            op = getattr(tfm, name)
            setattr(tfm, name, lambda q, *a, _op=op, **kw: _op(
                float8_mantissa(q), *a, **kw))

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
    try:
        server, state = build_server(
            model="benchmark", stage_configs=[stage], host="127.0.0.1",
            port=0)
    except BaseException:
        # a set-up that failed half way (out of device memory) leaves
        # threads that would keep the process, and the chip, for ever
        import os
        import traceback

        traceback.print_exc()
        sys.stderr.flush()
        os._exit(1)
    try:
        engine = state.omni._omni.stages[0].engine
        if not engine.scheduler.kv.window:
            raise SystemExit("the cell's model has no sliding-window layer")
        check = serve.reference_check(state, engine, config, args.seed)
        peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                   for d in jax.local_devices())
    finally:
        state.shutdown()
        server.server_close()
    print(json.dumps({"workload": args.workload, "seed": args.seed,
                      "off": args.off,
                      "device": device, "ok": check["ok"],
                      "max_abs_diff": check.get("max_abs_diff"),
                      "tolerance": check.get("tolerance"),
                      "memory_peak_bytes": peak,
                      "served": check.get("served"),
                      "reference": check.get("reference")}), flush=True)


if __name__ == "__main__":
    main()
