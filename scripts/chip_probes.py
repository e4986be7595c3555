"""Builder-run chip probes beside chip_smoke.py (CHANGES.md PR 21).

Each is one command for the chip tool, from the repo root, and exits
non-zero on failure.  They are not part of the driver's chip check.

    chiprun -- python -m scripts.chip_probes proc-one
    chiprun --chips 4 -- python -m scripts.chip_probes proc-two
    chiprun -- python -m scripts.chip_probes int8-engine
    chiprun -- python -m scripts.chip_probes dit

proc-one     the OpenAI server over ONE process stage (devices=all): the
             parent answers HTTP, /metrics and /health and must never
             initialize a JAX backend while its worker holds the chip.
proc-two     two single-stage pipelines, each a process stage scoped to
             one chip ("0" / "1"), alive at the same time.
int8-engine  LLMEngine with kv_cache_dtype int8 against bf16 KV on a small
             kernel-tileable model: compiled kernels, no XLA fallback.
dit          one Qwen-Image real_q (60-layer, int4-resident) 1024 px
             request, 3 steps, through Omni.
"""

import json
import sys
import threading
import urllib.request


def tiny_tileable_factory(expect_devices=None):
    """model_factory for the process-stage probes: a one-layer model the
    paged kernels can tile (head_dim 128); asserts its worker is on the
    TPU and sees the number of chips it was scoped to."""
    import jax
    import jax.numpy as jnp

    from vllm_omni_tpu.models.common import transformer as tfm

    assert jax.default_backend() == "tpu", jax.default_backend()
    print(f"[worker] devices={jax.devices()}", flush=True)
    if expect_devices is not None:
        assert len(jax.devices()) == expect_devices, jax.devices()
    cfg = tfm.TransformerConfig(
        vocab_size=512, hidden_size=256, num_layers=1, num_heads=2,
        num_kv_heads=1, head_dim=128, intermediate_size=256)
    return tfm.init_params(jax.random.PRNGKey(0), cfg, jnp.bfloat16), cfg, None


def _proc_stage(devices, expect_devices):
    from vllm_omni_tpu.config.stage import StageConfig, StageRuntime

    return StageConfig(
        stage_id=0, stage_type="llm",
        runtime=StageRuntime(process=True, devices=devices),
        engine_args={
            "model_factory": "scripts.chip_probes:tiny_tileable_factory",
            "model_factory_args": {"expect_devices": expect_devices},
            "num_pages": 64, "page_size": 16, "max_model_len": 256},
        engine_input_source=[-1], final_output=True,
        final_output_type="text",
        default_sampling_params={"temperature": 0.0, "max_tokens": 4})


def _assert_parent_off_jax():
    import jax._src.xla_bridge as xb

    assert not xb.backends_are_initialized(), \
        "the parent initialized a JAX backend"


def proc_one():
    from vllm_omni_tpu.entrypoints.openai.api_server import build_server

    server, state = build_server(
        model="probe", stage_configs=[_proc_stage("all", None)],
        host="127.0.0.1", port=0)
    threading.Thread(target=server.serve_forever, daemon=True).start()
    base = f"http://127.0.0.1:{server.server_address[1]}"
    try:
        req = urllib.request.Request(
            base + "/v1/completions",
            data=json.dumps({"prompt": [5, 6, 7, 8], "max_tokens": 4,
                             "ignore_eos": True}).encode(),
            headers={"Content-Type": "application/json"})
        print("completion:",
              urllib.request.urlopen(req, timeout=300).read()[:200])
        for path in ("/metrics?format=json", "/metrics", "/health"):
            with urllib.request.urlopen(base + path, timeout=60) as r:
                print(path, r.status, len(r.read()))
        _assert_parent_off_jax()
    finally:
        server.shutdown()
        state.shutdown()
        server.server_close()


def proc_two():
    from vllm_omni_tpu.entrypoints.omni import Omni

    a = Omni(stage_configs=[_proc_stage("0", 1)])
    try:
        b = Omni(stage_configs=[_proc_stage("1", 1)])
        try:
            for name, omni in (("a", a), ("b", b)):
                out = omni.generate([[5, 6, 7, 8]])[0]
                assert not out.is_error, out.error_message
                print(name, "tokens", out.outputs[0].token_ids, flush=True)
            _assert_parent_off_jax()
        finally:
            b.shutdown()
    finally:
        a.shutdown()


def int8_engine():
    import jax
    import jax.numpy as jnp
    import numpy as np

    from vllm_omni_tpu.engine import EngineConfig, LLMEngine
    from vllm_omni_tpu.models.common import transformer as tfm
    from vllm_omni_tpu.ops import _dispatch
    from vllm_omni_tpu.sampling_params import SamplingParams

    assert jax.default_backend() == "tpu", jax.default_backend()
    cfg = tfm.TransformerConfig(
        vocab_size=4096, hidden_size=512, num_layers=2, num_heads=8,
        num_kv_heads=1, head_dim=128, intermediate_size=1024)
    params = tfm.init_params(jax.random.PRNGKey(0), cfg, jnp.bfloat16)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(3, 4096, n).tolist() for n in (5, 40, 130)]
    sp = SamplingParams(temperature=0.0, max_tokens=12, ignore_eos=True,
                        logprobs=3)
    streams = {}
    for kv_dtype in ("auto", "int8"):
        engine = LLMEngine(params, cfg, EngineConfig(
            num_pages=128, page_size=16, max_model_len=512, max_num_seqs=4,
            max_num_batched_tokens=256, kv_cache_dtype=kv_dtype))
        streams[kv_dtype] = [
            (o.outputs[0].token_ids,
             [e["logprob"] for e in o.outputs[0].logprobs])
            for o in engine.generate([list(p) for p in prompts], sp)]
    assert not _dispatch.native_fallbacks, dict(_dispatch.native_fallbacks)
    for (ta, la), (tb, lb) in zip(streams["auto"], streams["int8"]):
        print(f"first-token logprob bf16 {la[0]:.4f} int8 {lb[0]:.4f}; "
              f"{sum(a == b for a, b in zip(ta, tb))}/{len(ta)} greedy "
              "tokens equal")
        assert np.isfinite(lb).all()
        assert ta[0] == tb[0] or abs(la[0] - lb[0]) < 0.1, (ta, tb)


def dit():
    import jax
    import numpy as np

    from vllm_omni_tpu.config.stage import StageConfig
    from vllm_omni_tpu.entrypoints.omni import Omni
    from vllm_omni_tpu.ops import _dispatch

    assert jax.default_backend() == "tpu", jax.default_backend()
    px = 1024
    stage = StageConfig(
        stage_id=0, stage_type="diffusion",
        engine_args={"model": "qwen-image-probe",
                     "model_arch": "QwenImagePipeline", "dtype": "bfloat16",
                     "size": "real_q", "quantization": "int4",
                     "default_height": px, "default_width": px},
        engine_input_source=[-1], final_output=True,
        final_output_type="image",
        default_sampling_params={
            "height": px, "width": px, "num_inference_steps": 3,
            "guidance_scale": 4.0, "seed": 0})
    omni = Omni(stage_configs=[stage])  # builds + 1-step warm-up
    try:
        stats = jax.devices()[0].memory_stats() or {}
        print("bytes_in_use after build:", stats.get("bytes_in_use"))
        out = omni.generate(["a photo of a cat"])[0]
        assert not out.is_error, out.error_message
        img = np.asarray(out.images[0])
        print("image", img.shape, img.dtype, int(img.min()), int(img.max()))
        assert img.shape[:2] == (px, px) and img.max() > img.min()
        assert not _dispatch.native_fallbacks, dict(
            _dispatch.native_fallbacks)
    finally:
        omni.shutdown()


if __name__ == "__main__":
    {"proc-one": proc_one, "proc-two": proc_two,
     "int8-engine": int8_engine, "dit": dit}[sys.argv[1]]()
    print(f"CHIP_PROBE_OK {sys.argv[1]}", flush=True)
