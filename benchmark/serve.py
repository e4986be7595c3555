"""Set-up of one cell: the configuration file becomes a served model.

Weights are made on the device(s) in one jitted call from the seed, in the
type they are served in; the OpenAI server is the program's own
(``build_server`` -> ``AsyncOmni`` -> ``LLMEngine``), with engine defaults
except the limits the configuration file writes down.
"""

from __future__ import annotations

import functools
import json
import subprocess
import sys
import time
import urllib.request
from pathlib import Path

import numpy as np

from benchmark import spec


def model_factory(config: dict, seed: int, timings: dict):
    """``model_factory`` of the stage: seeded random weights generated on
    the device(s) by the configuration's architecture module — under
    tensor parallelism directly in the engine's sharded layout, so that no
    chip ever holds the whole tree."""
    import jax
    import jax.numpy as jnp

    arch = spec.arch_of(config)
    cfg = arch.program_config(config)  # imports the program: not weights
    t0 = time.monotonic()
    init = functools.partial(arch.init_weights, cfg=cfg,
                             dtype=jnp.dtype(config["serve_dtype"]))
    tp = config.get("tensor_parallel_size", 1)
    shardings = None
    if tp > 1:
        from jax.sharding import Mesh, NamedSharding, PartitionSpec

        from vllm_omni_tpu.parallel.mesh import AXIS_TP

        mesh = Mesh(np.array(jax.devices()[:tp]), (AXIS_TP,))
        specs = arch.param_specs(
            jax.eval_shape(init, jax.random.PRNGKey(0)))
        shardings = jax.tree.map(
            lambda s: NamedSharding(mesh, s), specs,
            is_leaf=lambda x: isinstance(x, PartitionSpec))
    # --seed may exceed 32 signed bits; a key takes less
    key = jax.random.PRNGKey(seed % (2 ** 31 - 1))
    params = jax.block_until_ready(
        jax.jit(init, out_shardings=shardings)(key))
    timings["weights_s"] = time.monotonic() - t0
    return params, cfg, None


def start_server(config: dict, seed: int, timings: dict):
    """(server, state, engine); ``timings`` gains ``weights_s`` and
    ``warmup_s`` (every executable of the token and batch buckets the
    engine's limits reach: compiled, or loaded from the cache)."""
    from vllm_omni_tpu.config.stage import StageConfig
    from vllm_omni_tpu.entrypoints.openai.api_server import build_server

    stage = StageConfig(
        stage_id=0, stage_type="llm",
        engine_args={
            "model_factory": model_factory,
            "model_factory_args": {"config": config, "seed": seed,
                                   "timings": timings},
            "warmup": True,
            "tensor_parallel_size": config.get("tensor_parallel_size", 1),
            **config["engine"],
        },
        engine_input_source=[-1], final_output=True,
        final_output_type="text",
    )
    t0 = time.monotonic()
    server, state = build_server(model="benchmark", stage_configs=[stage],
                                 host="127.0.0.1", port=0)
    timings["warmup_s"] = time.monotonic() - t0 - timings["weights_s"]
    return server, state, state.omni._omni.stages[0].engine


def http_json(port: int, path: str, body: dict | None = None,
              timeout: float = 300.0) -> dict:
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}",
        data=None if body is None else json.dumps(body).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        return json.loads(resp.read())


def engine_snapshot(port: int) -> dict:
    """The engine's counters and host-clock histograms, as the server's
    ``/metrics?format=json`` gives them."""
    return http_json(port, "/metrics?format=json")["engines"]["0"]


def drive(port: int, traffic: dict, seed: int, seconds: float,
          template: int, in_window=None) -> dict:
    """Offer ``traffic`` to the server for its lead-in and ``seconds`` of
    window, from a process of its own (off JAX and off this interpreter's
    lock); it connects first and is then told when to start.  Returns its
    records beside the engine's snapshots at the window's edges and the
    flight-recorder records of the window.  ``in_window(w0)`` runs in this
    thread once the window has begun (the traced part of a run)."""
    gen = subprocess.Popen(
        [sys.executable, str(Path(__file__).with_name("loadgen.py"))],
        stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True)
    try:
        gen.stdin.write(json.dumps({
            "host": "127.0.0.1", "port": port, "traffic": traffic,
            "seed": seed, "seconds": seconds,
            "template_tokens": template}) + "\n")
        gen.stdin.flush()
        connect_ms = json.loads(gen.stdout.readline())["connect_ms"]
        t0 = time.monotonic() + 0.5
        w0 = t0 + traffic["lead_in_s"]
        w1 = w0 + seconds
        gen.stdin.write(json.dumps({"t0": t0}) + "\n")
        gen.stdin.close()
        gen.stdin = None  # or communicate() below would flush it again
        before = engine_snapshot(port)
        time.sleep(max(w0 - time.monotonic(), 0))
        engine0, wall0 = engine_snapshot(port), time.time()
        if in_window is not None:
            in_window(w0)
        time.sleep(max(w1 - time.monotonic(), 0))
        engine1, wall1 = engine_snapshot(port), time.time()
        steps = [s for s in http_json(port, "/debug/flightrecorder?n=16384")[
            "stages"]["0"]["records"] if wall0 <= s["ts"] <= wall1]
        out, _ = gen.communicate(timeout=traffic["request_timeout_s"] + 60)
        if gen.returncode != 0:
            raise SystemExit(f"benchmark: the load generator exited with "
                             f"{gen.returncode}")
    finally:
        if gen.poll() is None:
            gen.kill()
            gen.wait()
    return {"records": json.loads(out.strip().splitlines()[-1])["records"],
            "connect_ms": connect_ms, "window": (w0, w1),
            "engine_before": before, "engine0": engine0, "engine1": engine1,
            "engine_after": engine_snapshot(port), "steps": steps}


def template_tokens(port: int, traffic: dict) -> int:
    """Tokens the chat template and the tokenizer add around a message,
    calibrated against ``usage.prompt_tokens`` of one sampled request (the
    sampler's path is then warm too)."""
    probe = "calibrate " * 4
    reply = http_json(port, "/v1/chat/completions", {
        "messages": [{"role": "user", "content": probe}],
        "max_tokens": 2, "ignore_eos": True, "seed": 0,
        **traffic["sampling"]})
    if reply["usage"]["completion_tokens"] != 2:
        raise SystemExit(f"benchmark: asked 2 tokens, got {reply['usage']}")
    return reply["usage"]["prompt_tokens"] - len(probe)


#: Largest difference allowed between the logprob the server reports for a
#: token it generated and the float32 reference's log-softmax for it.  The
#: server computes in bfloat16 (8 bits of mantissa: 2^-8 = 0.004 relative on
#: each rounding) through 8 to 20 layers into logits of a few units, and a
#: log-softmax over 152064 entries; PR 21 read differences of 0.016 to 0.048
#: between two bfloat16 paths of the same model.  An int8 cache or fp8
#: weights move logits by several tenths and fail this.
LOGPROB_TOL = 0.15
#: lengths of the check where the configuration file gives none under
#: ``reference_check``
CHECK_LENGTHS = {"prompt_tokens": 40, "new_tokens": 8}


def reference_check(state, engine, config: dict, seed: int) -> dict:
    """One seeded prompt, decoded greedily by the served engine — the first
    token from prefill, the rest through the paged cache — against the
    plain float32 forward over the same ids, which the configuration's
    architecture module gives (``reference_logprobs``).  The file's
    ``reference_check`` sets the lengths, so that a configuration can
    check past a window or at a context where its cache layout matters."""
    lengths = {**CHECK_LENGTHS, **config.get("reference_check", {})}
    rng = np.random.default_rng(seed)
    prompt = rng.integers(3, config["vocab_size"],
                          lengths["prompt_tokens"]).tolist()
    n_new = lengths["new_tokens"]
    outs = state.collect(
        {"prompt_token_ids": prompt},
        {"max_tokens": n_new, "temperature": 0.0, "ignore_eos": True,
         "logprobs": 0})
    out = next(o for o in outs if o.outputs)
    ids = [int(t) for t in out.outputs[0].token_ids]
    served = [float(e["logprob"]) for e in out.outputs[0].logprobs]
    if len(ids) != n_new or len(served) != n_new:
        return {"ok": False, "why": f"asked {n_new} tokens, got {len(ids)}"}
    ref = spec.arch_of(config).reference_logprobs(
        engine.runner.params, config, prompt + ids[:-1], n_new,
        tp=config.get("tensor_parallel_size", 1))
    want = [float(ref[i, t]) for i, t in enumerate(ids)]
    diff = max(abs(a - b) for a, b in zip(served, want))
    return {"ok": bool(np.isfinite(diff) and diff <= LOGPROB_TOL),
            "max_abs_diff": diff, "tolerance": LOGPROB_TOL,
            "served": served, "reference": want}
