"""What the benchmark's reference check reads for a model with
sliding-window layers (and learned sparse attention, or rotary on one kind
of layer) when one of the things it is there to see is taken out of the
SERVED path: the controls that say what the check's limit can see (ISSUE
34; ISSUE 50: ``--workload commandaplus_agent_sat --off window | nope |
precision``; ISSUE 55: ``--workload mimov25_agent_sat --off sink | window |
vscale | precision``).

    chiprun -- python3 -m scripts.sparse_window_control \
        --workload dots3note_agent_sat --seed 3400000101 --off selection

The cell's configuration is served as ``benchmark/run.py`` serves it
(weights from the seed, ``build_server``), without warm-up: only the
executables the check's prompt and its decoded tokens reach are compiled.
``--off none`` is the served program; ``selection`` makes the full layers
attend every visible key (the indexer still runs and scores; its selection
is replaced by "everything it saw"); ``window`` makes the sliding layers
attend their whole context (the kernels are not told the window, and no
window page is given back); ``nope`` rotates the queries and keys of the
layers that a model with ``rope_kinds`` leaves without positions (the
configuration's ``rope_kinds`` is not read); ``sink`` leaves the learned
sink out of the sliding layers' softmax (the kernels are not handed the
layer's ``sinks``); ``vscale`` leaves the values unscaled
(``attention_value_scale`` read as 1); ``precision`` gives paged
attention its
operands (the queries, every cached row, and the index keys) with float8's
three bits of mantissa where the configuration states bfloat16's seven
(the nearest precision below it).  Each is done
HERE, on the program's functions, and is no option of the program.  The
reference stays as it is.  ``--seed`` takes several: the engine is built
and its executables compiled once, and for every further seed the weights
are drawn anew in the running engine (the old tree deleted first: two do
not fit a chip) and the check is run on that seed's own prompt, which
costs seconds where a process of its own costs a minute.  Prints one JSON
line a seed with the check's result.  ``--picks`` adds one line: where the
router of an expert-parallel share sends a prompt AS THE CELL'S TRAFFIC
MAKES THEM (seeded printable bytes through the byte tokenizer, 8 192 of
them), layer by layer from the reference's own forward: the share of a
token's picks that land on an expert held here and the held experts a
2 048-token chunk touches, beside what the architecture module's counts
expect.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))


def picks_here(arch, params, config: dict, seed: int,
               tokens: int = 8192, chunk: int = 2048) -> dict:
    """Per layer and chunk of a byte prompt: the share of the router's
    picks that land on the held experts, and how many of them a chunk
    touches (the reference's float32 forward, ``arch._layer``)."""
    import random

    import jax
    import jax.numpy as jnp

    from benchmark import traffic

    rng = random.Random(f"{seed}:picks")
    ids = jnp.asarray([3 + ord(c) for c in rng.choices(traffic.ALPHABET,
                                                        k=tokens)])
    held, off = config["num_experts"], config.get("expert_offset", 0)
    hps = arch._layer_hps(config)
    share, touched = [], []
    with jax.default_matmul_precision("highest"):
        x = params["embed"]["w"][ids].astype(jnp.float32)
        for layer, kind in zip(params["layers"], config["layer_types"]):
            h = arch._ln(x, layer["input_norm"]["w"].astype(jnp.float32),
                         config["layer_norm_eps"])
            _, top = jax.lax.top_k(
                h @ layer["router"]["w"].astype(jnp.float32),
                config["num_experts_per_tok"])
            top = top.reshape(-1, chunk, top.shape[-1])
            here = (top >= off) & (top < off + held)
            share.append([round(float(c.mean()), 4) for c in here])
            touched.append([len(set(t[m].tolist()))
                            for t, m in zip(jax.device_get(top),
                                            jax.device_get(here))])
            x = arch._layer(layer, x, hps[kind])
    costs = arch.model_costs(config)
    return {"picks": "bytes", "seed": seed, "chunk_tokens": chunk,
            "share_here_by_layer_chunk": share,
            "share_expected": held / costs["width"],
            "held_touched_by_layer_chunk": touched,
            "held_touched_expected": arch._touched(costs, chunk)}


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, nargs="+", required=True)
    ap.add_argument("--off", default="none",
                    choices=("none", "selection", "window", "nope", "sink",
                             "vscale", "precision"))
    ap.add_argument("--picks", action="store_true")
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
    elif args.off == "sink":
        for name in ("paged_attention", "ragged_paged_attention"):
            op = getattr(tfm, name)
            setattr(tfm, name, lambda *a, _op=op, **kw: _op(
                *a, **{k: v for k, v in kw.items() if k != "sinks"}))
    elif args.off == "vscale":
        qkv = tfm._qkv
        tfm._qkv = lambda layer, cfg, x: qkv(
            layer, dataclasses.replace(cfg, attention_value_scale=1.0), x)
    elif args.off == "nope":
        kind_ropes = tfm._kind_ropes
        tfm._kind_ropes = lambda cfg, *a: kind_ropes(
            dataclasses.replace(cfg, rope_kinds=None), *a)
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
            "model_factory_args": {"config": config, "seed": args.seed[0],
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
        kv = engine.scheduler.kv
        if not kv.window:
            raise SystemExit("the cell's model has no sliding-window layer")
        for i, seed in enumerate(args.seed):
            if i:
                old, engine.runner.params = engine.runner.params, None
                for leaf in jax.tree.leaves(old):
                    leaf.delete()
                engine.runner.params = serve.model_factory(
                    config, seed, {})[0]
            freed = kv.window_pages_freed
            check = serve.reference_check(state, engine, config, seed)
            peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                       for d in jax.local_devices())
            print(json.dumps({
                "workload": args.workload, "seed": seed, "off": args.off,
                "window_pages_freed": kv.window_pages_freed - freed,
                "device": device, "ok": check["ok"],
                "max_abs_diff": check.get("max_abs_diff"),
                "tolerance": check.get("tolerance"),
                "memory_peak_bytes": peak,
                "served": check.get("served"),
                "reference": check.get("reference")}), flush=True)
        if args.picks:
            print(json.dumps(picks_here(
                spec.arch_of(config), engine.runner.params, config,
                args.seed[-1])), flush=True)
    finally:
        state.shutdown()
        server.server_close()

if __name__ == "__main__":
    main()
