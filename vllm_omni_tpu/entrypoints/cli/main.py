"""CLI: ``python -m vllm_omni_tpu.entrypoints.cli <cmd>``, with ``<cmd>`` one
of ``serve``, ``generate``, ``bench-serve``, ``serve-stage``.

The TPU-native analogue of the reference's ``vllm serve <model> --omni``
interception (reference: entrypoints/cli/main.py:10-17, OmniServeCommand
cli/serve.py:42 with diffusion autodetect :55-63).
"""

from __future__ import annotations

import argparse
import json
import sys


def _add_common(p: argparse.ArgumentParser):
    p.add_argument("model", nargs="?", default=None,
                   help="model name/path (resolves an in-tree stage YAML)")
    p.add_argument("--stage-configs-path", default=None,
                   help="explicit stage-config YAML (overrides model lookup)")
    # reference-style engine arg surface (vllm serve flags; reference:
    # entrypoints/cli/serve.py + omni engine args) — applied to the
    # ENTRY stage; use --stage-override for other stages
    eng = p.add_argument_group("engine args (entry stage)")
    eng.add_argument("--tensor-parallel-size", type=int, default=None)
    eng.add_argument("--max-model-len", type=int, default=None)
    eng.add_argument("--max-num-seqs", type=int, default=None)
    eng.add_argument("--max-num-batched-tokens", type=int, default=None)
    eng.add_argument("--dtype", default=None,
                     help="bfloat16|float32|float16 — engine compute/"
                          "KV-cache dtype; WEIGHT dtype comes from the "
                          "stage YAML's model_factory_args")
    eng.add_argument("--seed", type=int, default=None)
    eng.add_argument("--enable-chunked-prefill", action="store_true",
                     default=None)
    eng.add_argument("--num-speculative-tokens", type=int, default=None)
    eng.add_argument("--async-scheduling", action="store_true",
                     default=None,
                     help="two-slot pipelined engine step: overlap host "
                          "scheduling/readback with device compute via "
                          "device-resident sampled tokens.  The default "
                          "for an AR stage since PR 38, so the flag only "
                          "says what is already so (see "
                          "docs/async_engine.md)")
    eng.add_argument("--unified-batching", action="store_true",
                     default=None,
                     help="unified scheduler packing policy: decodes "
                          "claim the token budget first and chunked "
                          "prefill becomes the mechanism.  Execution "
                          "is always unified — every non-pure-decode "
                          "step is ONE token-packed ragged dispatch "
                          "(see docs/ragged_batching.md)")
    eng.add_argument("--kv-offload", action="store_true", default=None,
                     help="tiered KV offload: evicted prefix-cache "
                          "pages and preempted requests park their KV "
                          "in a host-RAM pool (and optionally a remote "
                          "store) instead of recomputing (see "
                          "docs/kv_cache.md)")
    eng.add_argument("--kv-offload-quant", default=None,
                     choices=("none", "int8"),
                     help="cold-path payload storage: none keeps "
                          "restores bit-exact, int8 halves the bytes "
                          "moved between host and HBM")
    eng.add_argument("--kv-cache-dtype", default=None,
                     choices=("auto", "int8", "bf16"),
                     help="HBM-RESIDENT paged-KV layout: int8 stores "
                          "the page pool as int8 + per-(head, page) "
                          "scales dequantized in-kernel — ~2x pages "
                          "(sessions) in the same HBM budget (see "
                          "docs/performance.md); auto/bf16 keep the "
                          "dense layout in the model dtype")
    eng.add_argument("--kv-offload-policy", default=None,
                     choices=("auto", "always", "never"),
                     help="bytes-vs-recompute admission: auto runs the "
                          "break-even math (kvcache/policy.py), "
                          "always/never pin the decision")
    eng.add_argument("--kv-host-tier-bytes", type=int, default=None,
                     help="host-RAM tier capacity; overflow demotes "
                          "LRU payloads to the remote connector (or "
                          "drops them without one)")
    eng.add_argument("--kv-offload-connector", default=None,
                     help="remote-tier transport: a connector name "
                          "(inproc|shm|tcp) wired with retry + circuit "
                          "breaker on the edge")
    eng.add_argument("--slo-ttft-ms", type=float, default=None,
                     help="per-request TTFT SLO target: finished "
                          "requests are judged against it per tenant "
                          "(slo_attainment_ratio / goodput_tokens_total "
                          "on /metrics; see docs/load_testing.md)")
    eng.add_argument("--slo-tpot-ms", type=float, default=None,
                     help="per-request TPOT (time per output token) "
                          "SLO target")
    eng.add_argument("--max-queue-depth", type=int, default=None,
                     help="admission control: arrivals past this "
                          "waiting-queue depth are shed with HTTP 429 "
                          "(shed_requests_total{reason=queue_depth}) "
                          "instead of queued into a wait they can only "
                          "lose")
    eng.add_argument("--wfq-scheduling", action="store_true",
                     default=None,
                     help="weighted-fair overload scheduling (docs/"
                          "control_plane.md): deficit-round-robin "
                          "admission over per-tenant priority weights "
                          "(x-omni-priority) and priority-ordered "
                          "shedding at max-queue-depth — low-priority "
                          "work defers under overload instead of "
                          "everyone starving equally")
    eng.add_argument("--engine-role", default=None,
                     choices=("prefill", "decode", "colocated"),
                     help="disaggregated serving role (docs/"
                          "disaggregation.md): prefill engines run "
                          "requests to the end of prompt processing "
                          "and ship paged KV to a decode tier "
                          "(kv_transfer auto-armed); decode engines "
                          "adopt streamed KV and resume as decode; "
                          "colocated (default) is the classic single-"
                          "engine shape")
    p.add_argument(
        "--stats-path", default=None, metavar="PREFIX",
        help="stream per-stage + E2E stats to PREFIX.*.stats.jsonl")
    p.add_argument(
        "--trace-path", default=None, metavar="PREFIX",
        help="per-request distributed traces: PREFIX.trace.jsonl + "
             "PREFIX.trace.json (Perfetto-loadable Chrome trace); see "
             "docs/observability.md")
    p.add_argument(
        "--stage-override", action="append", default=[],
        metavar="N.KEY=VALUE",
        help="set engine_args KEY of stage N (repeatable); VALUE parses "
             "as JSON when possible, e.g. --stage-override "
             "2.num_steps=4 --stage-override 1.dtype='\"float32\"'")


_ENTRY_FLAGS = ("tensor_parallel_size", "max_model_len", "max_num_seqs",
                "max_num_batched_tokens", "dtype", "seed",
                "enable_chunked_prefill", "num_speculative_tokens",
                "async_scheduling", "unified_batching",
                "kv_offload", "kv_offload_quant", "kv_cache_dtype",
                "kv_offload_policy",
                "kv_host_tier_bytes", "kv_offload_connector",
                "slo_ttft_ms", "slo_tpot_ms", "max_queue_depth",
                "wfq_scheduling", "engine_role")


def _stage_overrides(args) -> dict:
    """CLI flags -> the Omni constructor's per-stage override dict
    ({"stage0": {...}, "stage2": {...}})."""
    out: dict[str, dict] = {}
    entry = {k: getattr(args, k) for k in _ENTRY_FLAGS
             if getattr(args, k, None) is not None}
    if entry:
        out["stage0"] = entry
    for item in args.stage_override:
        try:
            target, val = item.split("=", 1)
            stage, key = target.split(".", 1)
            stage_key = f"stage{int(stage)}"
        except ValueError:
            raise SystemExit(
                f"--stage-override expects N.KEY=VALUE, got {item!r}")
        try:
            val = json.loads(val)
        except json.JSONDecodeError:
            pass  # keep the raw string
        out.setdefault(stage_key, {})[key] = val
    return out


def cmd_serve(args) -> int:
    from vllm_omni_tpu.entrypoints.openai.api_server import run_server

    run_server(
        model=args.model,
        stage_configs=args.stage_configs_path,
        host=args.host,
        port=args.port,
        stats_path=args.stats_path,
        trace_path=args.trace_path,
        **_stage_overrides(args),
    )
    return 0


def cmd_generate(args) -> int:
    from vllm_omni_tpu.entrypoints.omni import Omni

    omni = Omni(model=args.model, stage_configs=args.stage_configs_path,
                stats_path=args.stats_path, trace_path=args.trace_path,
                **_stage_overrides(args))
    sp = json.loads(args.sampling_params) if args.sampling_params else {}
    outs = omni.generate([args.prompt], [sp])
    for o in outs:
        if o.final_output_type == "text" and o.outputs:
            print(o.outputs[0].text or o.outputs[0].token_ids)
        elif o.final_output_type == "image" and o.images:
            import numpy as np

            path = f"{o.request_id}.npy"
            np.save(path, np.asarray(o.images[0]))
            print(f"image saved to {path}")
        elif "audio" in o.multimodal_output:
            import numpy as np

            path = f"{o.request_id}.f32"
            np.asarray(o.multimodal_output["audio"],
                       dtype=np.float32).tofile(path)
            print(f"audio saved to {path}")
    print(json.dumps(omni.metrics.summary(), indent=2), file=sys.stderr)
    return 0


def cmd_serve_stage(args) -> int:
    from vllm_omni_tpu.entrypoints.stage_proc import run_remote_stage

    run_remote_stage(
        args.stage_configs, args.stage_id,
        connect=args.connect, discover=args.discover,
        retry_timeout=args.retry_timeout,
    )
    return 0


def cmd_bench_serve(args) -> int:
    from vllm_omni_tpu.benchmarks.serving import run_from_args

    return run_from_args(args)


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(prog="vllm-omni-tpu")
    sub = parser.add_subparsers(dest="cmd", required=True)

    serve = sub.add_parser("serve", help="start the OpenAI-compatible server")
    _add_common(serve)
    serve.add_argument("--host", default="0.0.0.0")
    serve.add_argument("--port", type=int, default=8000)
    serve.set_defaults(fn=cmd_serve)

    gen = sub.add_parser("generate", help="offline one-shot generation")
    _add_common(gen)
    gen.add_argument("--prompt", required=True)
    gen.add_argument("--sampling-params", default=None,
                     help='JSON, e.g. \'{"max_tokens": 32}\'')
    gen.set_defaults(fn=cmd_generate)

    bserve = sub.add_parser(
        "bench-serve",
        help="online serving benchmark against a running server "
             "(latency percentiles; reference: vllm bench serve --omni)",
    )
    from vllm_omni_tpu.benchmarks.serving import add_cli_args

    add_cli_args(bserve)
    bserve.set_defaults(fn=cmd_bench_serve)

    sstage = sub.add_parser(
        "serve-stage",
        help="run one pipeline stage as a REMOTE worker connecting to an "
             "orchestrator on another host (cross-host stage placement; "
             "reference: Ray per-node workers, ray_utils/utils.py)",
    )
    sstage.add_argument("--stage-configs", required=True,
                        help="stage YAML (same file the orchestrator uses)")
    sstage.add_argument("--stage-id", type=int, required=True)
    sstage.add_argument("--connect", default=None,
                        help="orchestrator listener host:port")
    sstage.add_argument("--discover", default=None,
                        help="KV-store address publishing stage listeners")
    sstage.add_argument("--retry-timeout", type=float, default=120.0)
    sstage.set_defaults(fn=cmd_serve_stage)

    args = parser.parse_args(argv)
    return args.fn(args)


if __name__ == "__main__":
    sys.exit(main())
