"""chip_smoke.py — the quickest proof the system still starts on the chip.

One process, three phases, any failure ends in a non-zero exit:

1. Device gate: JAX must have found a TPU whose ``device_kind`` the
   platform's peak table lists, with the Pallas kernels in native
   (Mosaic-compiled) mode.  No chip is an error, not a CPU run.
2. Kernel phase: every ``pl.pallas_call`` in ``vllm_omni_tpu/ops`` is
   compiled by Mosaic at the shapes the served model and the flagship DiT
   use, and compared on the device with its ``*_ref`` oracle.
3. Serve phase: the OpenAI server (``build_server`` -> ``AsyncOmni`` ->
   ``LLMEngine`` -> scheduler -> paged KV -> runner -> kernels) serves the
   Qwen3-Omni-30B-A3B thinker at its published widths (depth is the only
   cut; seeded random bf16 weights) and answers a handful of concurrent
   HTTP requests.

The last stdout line is one JSON object naming the device.  This is not a
benchmark: it prints set-up (compile) seconds and no rate.

    python chip_smoke.py [--tensor-parallel-size N]
"""

from __future__ import annotations

import argparse
import dataclasses
import functools
import importlib.metadata
import itertools
import json
import os
import sys
import threading
import time
import urllib.request
from typing import NoReturn

import numpy as np

#: thinker depth served, of 48 identical layers (~1.25 GB of bf16 each;
#: embedding + head another ~1.25 GB): what fits 16 GB with room for KV
LAYERS = 8

#: tolerance for bf16 kernel outputs against the f32 "highest" reference:
#: two bf16 roundings (2^-8 relative each) of O(1..4) values
BF16_ATOL = 3e-2

_T0 = time.monotonic()


def say(msg: str) -> None:
    print(f"[chip_smoke {time.monotonic() - _T0:6.1f}s] {msg}", flush=True)


def fail(msg: str) -> NoReturn:
    raise SystemExit(f"chip_smoke: FAIL: {msg}")


# ------------------------------------------------------------- device gate
def device_gate() -> dict:
    import jax
    import jaxlib

    backend = jax.default_backend()
    if backend != "tpu":
        fail(f"no TPU found: jax.default_backend() is {backend!r} "
             f"(JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS')!r}); this "
             "script only runs on the chip")
    if os.environ.get("OMNI_TPU_PALLAS_INTERPRET"):
        fail("OMNI_TPU_PALLAS_INTERPRET is set; the smoke needs the "
             "Mosaic-compiled kernels")
    from vllm_omni_tpu.ops._dispatch import pallas_mode
    from vllm_omni_tpu.platforms import current_platform

    platform = current_platform()
    if platform.name != "tpu" or pallas_mode() != "native":
        fail(f"platform {platform.name!r} / pallas_mode "
             f"{pallas_mode()!r}: expected tpu / native")
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    # raises on a device_kind the peak table does not list
    peaks = (platform.peak_tflops_bf16(), platform.peak_hbm_gbps())
    say(f"device gate: platform={device['platform']} "
        f"device_kind={device['kind']!r} count={device['count']} "
        f"peaks(table)={peaks[0]:g} TFLOP/s bf16, {peaks[1]:g} GB/s | "
        f"jax {jax.__version__} jaxlib {jaxlib.__version__} "
        f"libtpu {importlib.metadata.version('libtpu')}")
    return device


# ------------------------------------------------------------ kernel phase
def _kernel_cases():
    """(name, kernel_fn, ref_fn, args) per kernel x layout.  Shapes:
    the thinker's attention (32 q / 4 kv heads x 128, hidden 2048, page
    16), its per-shard view under TP=4 (1 kv head), and the Qwen-Image
    DiT joint sequence (4608 x 24 heads x 128)."""
    import jax
    import jax.numpy as jnp

    from vllm_omni_tpu import ops
    from vllm_omni_tpu.ops.paged_attention import init_kv_cache

    bf16 = jnp.bfloat16
    draws = itertools.count()

    def rnd(shape, dtype=bf16):
        key = jax.random.fold_in(jax.random.PRNGKey(0), next(draws))
        return jax.random.normal(key, shape, jnp.float32).astype(dtype)

    # --- rmsnorm: prefill rows, the 1-row decode case, qk-norm (hidden
    # = head_dim, a handful of rows in decode), fused residual
    for n, h in ((312, 2048), (1, 2048), (8 * 32, 128), (4, 128)):
        w = (1.0 + 0.1 * rnd((h,), jnp.float32)).astype(bf16)
        yield (f"rmsnorm[{n}x{h}]",
               lambda x, w: ops.rms_norm(x, w, use_pallas=True),
               ops.rms_norm_ref, (rnd((n, h)), w))
    w = (1.0 + 0.1 * rnd((2048,), jnp.float32)).astype(bf16)
    yield ("rmsnorm_fused_residual[256x2048]",
           lambda x, r, w: ops.rms_norm(x, w, residual=r, use_pallas=True),
           lambda x, r, w: ops.rms_norm_ref(x, w, residual=r),
           (rnd((256, 2048)), rnd((256, 2048)), w))

    # --- rope: q heads of a prefill bucket, kv heads of one decode row
    for t, h in ((256, 32), (1, 4)):
        cos, sin = ops.compute_rope_freqs(jnp.arange(t) + 1000, 128, 1e6)
        yield (f"rope[{t}x{h}x128]",
               lambda x, c, s: ops.apply_rope(x, c, s, use_pallas=True),
               ops.apply_rope_ref, (rnd((t, h, 128)), cos, sin))

    # --- flash attention (GQA 32/4, ragged length 517)
    b, s, hq, hkv, d = 2, 517, 32, 4, 128
    q, k, v = rnd((b, s, hq, d)), rnd((b, s, hkv, d)), rnd((b, s, hkv, d))
    kv_mask = (jnp.arange(s)[None, :]
               < jnp.array([[s], [400]])).astype(jnp.int32)
    q_chunk, q_off = rnd((b, 64, hq, d)), jnp.array([453, 100], jnp.int32)
    flash = functools.partial(ops.flash_attention, use_pallas=True)
    for name, kw, args in (
        ("noncausal", {}, (q, k, v)),
        ("causal", {"causal": True}, (q, k, v)),
        ("return_lse", {"return_lse": True}, (q, k, v)),
    ):
        yield (f"flash_{name}[2x517x32/4x128]",
               functools.partial(flash, **kw),
               functools.partial(ops.attention_ref, **kw), args)
    yield ("flash_kv_mask[2x517x32/4x128]",
           lambda q, k, v, m: flash(q, k, v, kv_mask=m),
           lambda q, k, v, m: ops.attention_ref(q, k, v, kv_mask=m),
           (q, k, v, kv_mask))
    yield ("flash_q_offsets[2x64@517x32/4x128]",
           lambda q, k, v, o: flash(q, k, v, causal=True, q_offsets=o),
           lambda q, k, v, o: ops.attention_ref(
               q, k, v, causal=True, q_offsets=o),
           (q_chunk, k, v, q_off))
    # the DiT joint shape ops/autotune.py was tuned for (text pad masked)
    jq, jk, jv = (rnd((1, 4608, 24, 128)) for _ in range(3))
    jmask = (jnp.arange(4608)[None, :] < 4500).astype(jnp.int32)
    yield ("flash_dit_joint[1x4608x24x128]",
           lambda q, k, v, m: flash(q, k, v, kv_mask=m),
           lambda q, k, v, m: ops.attention_ref(q, k, v, kv_mask=m),
           (jq, jk, jv, jmask))

    # --- paged decode + ragged paged attention, bf16 and int8 layouts
    def paged_inputs(hkv, page, quantized):
        """A paged cache filled through write_kv_cache, 8 sequences: five
        decode rows and three prefill chunks (one starting mid-context),
        in the token-packed layout of ops/ragged_paged_attention.py."""
        num_pages, max_pages, n_seq = 256, 32, 8
        (kc, vc), = init_kv_cache(1, num_pages, page, hkv, d, bf16,
                                  quantized=quantized)
        ctx = np.array([33, 64, 1, 100, 16, 7, 90, 257], np.int32)
        q_lens = np.array([1, 1, 1, 1, 1, 7, 90, 40], np.int32)
        perm = np.random.default_rng(0).permutation(num_pages)
        tables = perm[: n_seq * max_pages].reshape(n_seq, max_pages)
        seq = np.repeat(np.arange(n_seq), ctx)
        pos = np.concatenate([np.arange(n) for n in ctx])
        slots = tables[seq, pos // page] * page + pos % page
        kc, vc = ops.write_kv_cache(
            kc, vc, rnd((len(slots), hkv, d)), rnd((len(slots), hkv, d)),
            jnp.asarray(slots, jnp.int32))
        aligned = -(-q_lens // 8) * 8
        cu = np.concatenate([[0], np.cumsum(aligned)]).astype(np.int32)
        t_pad = 256  # the token bucket above cu[-1] == 184
        return (kc, vc, jnp.asarray(tables, jnp.int32), jnp.asarray(ctx),
                jnp.asarray(cu), jnp.asarray(q_lens), t_pad)

    for hkv_, page, quantized in ((4, 16, False), (4, 16, True),
                                  (4, 32, True), (1, 16, False),
                                  (1, 16, True)):
        kc, vc, tables, ctx, cu, q_lens, t_pad = paged_inputs(
            hkv_, page, quantized)
        layout = f"{'int8' if quantized else 'bf16'},page{page}"
        hq_ = hkv_ * 8
        yield (f"paged_decode[{layout},8x{hq_}/{hkv_}x128]",
               functools.partial(ops.paged_attention, use_pallas=True),
               ops.paged_attention_ref,
               (rnd((8, hq_, d)), kc, vc, tables, ctx))
        yield (f"ragged_paged[{layout},T{t_pad}x{hq_}/{hkv_}x128]",
               functools.partial(ops.ragged_paged_attention,
                                 use_pallas=True),
               ops.ragged_paged_attention_ref,
               (rnd((t_pad, hq_, d)), kc, vc, tables, cu, q_lens, ctx,
                jnp.array([8], jnp.int32)))


def kernel_phase() -> list[dict]:
    import jax
    import jax.numpy as jnp

    results = []
    for name, kernel_fn, ref_fn, args in _kernel_cases():
        t0 = time.monotonic()
        lowered = jax.jit(kernel_fn).lower(*args)
        if "tpu_custom_call" not in lowered.as_text():
            fail(f"{name}: lowering holds no Mosaic custom call — the "
                 "kernel did not take the native Pallas path")
        got = lowered.compile()(*args)
        with jax.default_matmul_precision("highest"):
            want = jax.jit(ref_fn)(*args)
        err = 0.0
        for g, w_ in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
            if g.shape != w_.shape:
                fail(f"{name}: shape {g.shape} != reference {w_.shape}")
            g32, w32 = g.astype(jnp.float32), w_.astype(jnp.float32)
            if not bool(jnp.all(jnp.isfinite(g32))):
                fail(f"{name}: non-finite kernel output")
            err = max(err, float(jnp.max(jnp.abs(g32 - w32))))
        say(f"kernel {name}: compiled (Mosaic), max_abs_err={err:.2e} "
            f"(atol {BF16_ATOL:g}), {time.monotonic() - t0:.1f}s incl. "
            "compile")
        if not err <= BF16_ATOL:
            fail(f"{name}: max_abs_err {err:.3e} > atol {BF16_ATOL:g}")
        results.append({"kernel": name, "max_abs_err": err})
    return results


# ------------------------------------------------------------- serve phase
def thinker_factory(layers: int, tensor_parallel_size: int):
    """model_factory: QWEN3_OMNI_THINKER_30B with depth cut to ``layers``,
    seeded random bf16 weights generated on the device(s) — under TP
    directly in the engine's sharded layout, so no chip ever holds the
    whole tree."""
    import jax
    import jax.numpy as jnp

    from vllm_omni_tpu.models.common import transformer as tfm
    from vllm_omni_tpu.models.qwen3_omni.thinker import (
        QWEN3_OMNI_THINKER_30B,
    )

    cfg = dataclasses.replace(QWEN3_OMNI_THINKER_30B, num_layers=layers)
    init = functools.partial(tfm.init_params, cfg=cfg, dtype=jnp.bfloat16)
    shardings = None
    if tensor_parallel_size > 1:
        from jax.sharding import Mesh, NamedSharding, PartitionSpec

        from vllm_omni_tpu.parallel.mesh import AXIS_TP
        from vllm_omni_tpu.parallel.sharding import ar_param_specs_tree

        mesh = Mesh(np.array(jax.devices()[:tensor_parallel_size]),
                    (AXIS_TP,))
        specs = ar_param_specs_tree(
            jax.eval_shape(init, jax.random.PRNGKey(0)))
        shardings = jax.tree.map(
            lambda spec: NamedSharding(mesh, spec), specs,
            is_leaf=lambda x: isinstance(x, PartitionSpec))
    params = jax.jit(init, out_shardings=shardings)(jax.random.PRNGKey(0))
    return params, cfg, None


def _http(base: str, path: str, body: dict | None = None,
          timeout: float = 300.0):
    """One HTTP exchange -> (status, parsed).  A streamed chat reply parses
    to the list of its SSE data events."""
    req = urllib.request.Request(
        base + path,
        data=None if body is None else json.dumps(body).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        raw = resp.read().decode()
        if body is not None and body.get("stream"):
            events = [ln[5:].strip() for ln in raw.splitlines()
                      if ln.startswith("data:")]
            if not events or events[-1] != "[DONE]":
                fail(f"{path}: SSE stream did not end in [DONE]")
            return resp.status, [json.loads(e) for e in events[:-1]]
        if resp.headers.get_content_type() == "application/json":
            return resp.status, json.loads(raw)
        return resp.status, raw


def _check_logprobs(entries: list, want_tokens: int, what: str) -> None:
    if len(entries) != want_tokens:
        fail(f"{what}: {len(entries)} tokens, asked {want_tokens}")
    vals = [e["logprob"] for e in entries] + [
        t["logprob"] for e in entries for t in e["top_logprobs"]]
    if not all(np.isfinite(v) and v <= 1e-3 for v in vals):
        fail(f"{what}: logprobs not finite / not <= 0: {vals[:8]}")


def serve_phase(layers: int, tensor_parallel_size: int) -> dict:
    import jax

    from vllm_omni_tpu.config.stage import StageConfig
    from vllm_omni_tpu.entrypoints.openai.api_server import build_server

    # JAX's own count of backend compilations, to cross-check the
    # runner's compile_stats (which only sees its own signatures)
    compiles = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, secs, **kw: compiles.append(secs)
        if event == "/jax/core/compile/backend_compile_duration" else None)

    stage = StageConfig(
        stage_id=0, stage_type="llm",
        engine_args={
            "model_factory": thinker_factory,
            "model_factory_args": {
                "layers": layers,
                "tensor_parallel_size": tensor_parallel_size},
            # defaults everywhere else; limits sized so that warm-up
            # (one executable per token bucket + two per decode bucket,
            # each paying the full-vocab sampler's compile) fits the
            # smoke's time limit
            "warmup": True,
            "max_num_seqs": 8, "max_num_batched_tokens": 256,
            "max_model_len": 1024, "num_pages": 512,
            "tensor_parallel_size": tensor_parallel_size,
        },
        engine_input_source=[-1], final_output=True,
        final_output_type="text",
        default_sampling_params={"temperature": 0.0},
    )
    say(f"serve: building server — thinker hidden 2048, 32q/4kv x 128, "
        f"128 experts x 768 top-8, vocab 152064, depth {layers}/48, "
        f"tensor_parallel_size={tensor_parallel_size}")
    t0 = time.monotonic()
    server, state = build_server(model="qwen3-omni-thinker-smoke",
                                 stage_configs=[stage],
                                 host="127.0.0.1", port=0)
    setup_s = time.monotonic() - t0
    engine = state.omni._omni.stages[0].engine
    warm = dict(engine.runner.compile_stats)
    jax_compiles_ready = len(compiles)
    say(f"serve: ready — set-up {setup_s:.1f}s (weights + warm-up: "
        f"{warm['compiles']} executables, {warm['compile_s']:.1f}s in "
        f"the runner; {jax_compiles_ready} JAX backend compiles, "
        f"{sum(compiles):.1f}s)")
    per_device = [
        (d.memory_stats() or {}).get("bytes_in_use", 0)
        for d in jax.local_devices()[:tensor_parallel_size]]
    say("serve: bytes in use per device after warm-up: "
        + ", ".join(f"{b / 2**30:.2f} GiB" for b in per_device))
    if tensor_parallel_size > 1 and min(per_device) < 0.9 * max(per_device):
        fail(f"weights/KV not spread evenly over the mesh: {per_device}")

    serve_thread = threading.Thread(target=server.serve_forever,
                                    daemon=True)
    serve_thread.start()
    try:
        traffic = _drive_traffic(
            f"http://127.0.0.1:{server.server_address[1]}")
    finally:
        server.shutdown()
        state.shutdown()
        server.server_close()
    serve_thread.join(timeout=10)
    if serve_thread.is_alive() or state.omni.engine_thread_alive:
        fail("server or engine thread still alive after shutdown")

    runner_compiles = engine.runner.compile_stats["compiles"] - warm[
        "compiles"]
    late = compiles[jax_compiles_ready:]
    say(f"serve: {traffic['requests_answered']} HTTP requests answered, "
        f"{traffic['engine_steps']} engine steps "
        f"({traffic['mixed_steps']} mixed prefill+decode), prefix-cache "
        f"hit tokens {traffic['prefix_hit_tokens']}; compilations after "
        f"warm-up: runner {runner_compiles}, JAX backend {len(late)}")
    if runner_compiles or late:
        fail(f"compilation after warm-up: runner {runner_compiles}, JAX "
             f"backend {len(late)} ({sum(late):.2f}s)")
    from vllm_omni_tpu.ops import _dispatch

    if _dispatch.native_fallbacks:
        fail("ops auto-dispatched to their XLA reference path on the "
             f"TPU platform: {dict(_dispatch.native_fallbacks)}")
    return {
        "layers": layers, "tensor_parallel_size": tensor_parallel_size,
        "setup_s": round(setup_s, 1),
        "warmup_executables": warm["compiles"],
        "compiles_after_warmup": runner_compiles + len(late),
        "bytes_in_use_per_device": per_device,
        **traffic,
    }


def _drive_traffic(base: str) -> dict:
    """Send the smoke's requests to the live server and check every reply;
    returns counts plus the first request's leading tokens (the greedy
    probe compared between the one-chip and the four-chip run)."""
    for _ in range(100):
        status, health = _http(base, "/health")
        if status == 200:
            break
        time.sleep(0.1)
    else:
        fail(f"/health never turned 200: {health}")

    def chat(text, **kw):
        return {"messages": [{"role": "user", "content": text}],
                "ignore_eos": True, **kw}

    long_ids = np.random.default_rng(0).integers(3, 152064, 200).tolist()
    repeat_msg = "smoke " * 20  # ~130 byte tokens: 8 full pages
    # (path, body, tokens asked); the first, a long SSE generation, is
    # decoding while the others arrive, so their prefills share steps
    # with its decode rows
    jobs = [
        ("/v1/chat/completions",
         chat("first", max_tokens=160, stream=True, logprobs=True,
              top_logprobs=5), 160),
        ("/v1/chat/completions", chat("hello there", max_tokens=12), 12),
        # 201 prompt tokens over the whole vocabulary, its last id
        # included: 13 pages, past the 128-token bucket
        ("/v1/completions",
         {"prompt": long_ids + [152063], "max_tokens": 8,
          "ignore_eos": True, "logprobs": 0}, 8),
        # sampled row: temperature / top-k / top-p run on the device
        ("/v1/chat/completions",
         chat("sampled", max_tokens=10, temperature=0.8, top_k=50,
              top_p=0.9, seed=7), 10),
        ("/v1/chat/completions",
         chat("streamed", max_tokens=9, stream=True, logprobs=True,
              top_logprobs=0), 9),
        ("/v1/chat/completions",
         chat(repeat_msg, max_tokens=6, logprobs=True, top_logprobs=5), 6),
    ]
    replies: list = [None] * len(jobs)

    def run(i):
        try:
            replies[i] = _http(base, *jobs[i][:2])
        except BaseException as e:  # re-raised in the main thread
            replies[i] = e

    threads = [threading.Thread(target=run, args=(i,))
               for i in range(len(jobs))]
    threads[0].start()
    time.sleep(0.5)  # the long request is admitted and decoding
    for t in threads[1:]:
        t.start()
    for t in threads:
        t.join(timeout=600)
        if t.is_alive():
            fail("a request did not finish within 600 s")

    def checked_tokens(what, path, body, want, reply) -> list:
        """Logprob entries (one per generated token) of a checked reply;
        [] where only the usage block counts tokens."""
        if isinstance(reply, BaseException):
            raise reply
        status, parsed = reply
        if status != 200:
            fail(f"{what}: HTTP {status}")
        if body.get("stream"):
            entries = [e for ev in parsed
                       for e in ev["choices"][0]["logprobs"]["content"]]
        elif path == "/v1/completions":
            entries = [
                {"logprob": v, "top_logprobs": []} for v in
                parsed["choices"][0]["logprobs"]["token_logprobs"]]
        elif body.get("logprobs"):
            entries = parsed["choices"][0]["logprobs"]["content"]
        else:
            got = parsed["usage"]["completion_tokens"]
            if got != want:
                fail(f"{what}: {got} tokens, asked {want}")
            return []
        _check_logprobs(entries, want, what)
        return entries

    tokens = [checked_tokens(f"request {i}", *job, reply)
              for i, (job, reply) in enumerate(zip(jobs, replies))]
    # the repeated prompt, now served from the prefix cache: its first
    # token must agree with the cold run's (or tie with it)
    c0 = tokens[5][0]
    h0 = checked_tokens("repeated request", *jobs[5],
                        _http(base, *jobs[5][:2]))[0]
    if c0["token"] != h0["token"]:
        alt = [t["logprob"] for t in c0["top_logprobs"]
               if t["token"] == h0["token"]]
        if not alt or c0["logprob"] - alt[0] > 0.05:
            fail("prefix-cached reply disagrees with the cold one: "
                 f"{c0} vs {h0}")
    elif abs(c0["logprob"] - h0["logprob"]) > 0.05:
        fail(f"prefix-cached first-token logprob drifted: {c0} vs {h0}")

    status, metrics = _http(base, "/metrics?format=json")
    if status != 200 or metrics["device"]["platform"] != "tpu":
        fail(f"/metrics device block: {metrics.get('device')}")
    hit_tokens = metrics["stages"]["0"]["prefix_cache"]["hit_tokens"]
    if hit_tokens < 128:
        fail(f"prefix cache hit only {hit_tokens} tokens")
    status, text = _http(base, "/metrics")
    if status != 200 or "vllm_omni_tpu_" not in text:
        fail("/metrics Prometheus exposition missing")
    _, flight = _http(base, "/debug/flightrecorder?n=256")
    steps = flight["stages"]["0"]["records"]
    mixed = sum(1 for s in steps if s["prefills"] and s["decodes"])
    if not mixed:
        fail("no engine step mixed prefill chunks with decode rows")
    status, health = _http(base, "/health")
    if status != 200:
        fail(f"/health after traffic: {status} {health}")
    return {
        "requests_answered": len(jobs) + 1,
        "engine_steps": len(steps), "mixed_steps": mixed,
        "prefix_hit_tokens": hit_tokens,
        "greedy_probe": [
            {"token": e["token"], "logprob": round(e["logprob"], 4),
             "top": [(t["token"], round(t["logprob"], 4))
                     for t in e["top_logprobs"]]}
            for e in tokens[0][:16]],
    }


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--tensor-parallel-size", type=int, default=1)
    args = ap.parse_args()

    device = device_gate()
    kernels = kernel_phase()
    serve = serve_phase(LAYERS, args.tensor_parallel_size)
    say(f"all phases passed in {time.monotonic() - _T0:.0f}s")
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/chip_smoke.json", "w") as f:
        json.dump({"device": device, "kernels": kernels, "serve": serve},
                  f, indent=1)
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()
