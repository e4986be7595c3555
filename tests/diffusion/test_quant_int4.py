"""int4 weight-only quantization: two nibbles packed per int8 byte.

The packing exists for one load-bearing reason: 4x-smaller weights fit
the FULL 60-layer Qwen-Image DiT (41 GB bf16 -> 10.3 GB) resident in a
single 16 GB chip's HBM, turning the flagship bench number from an
extrapolation into a measurement when host->HBM bandwidth can't sustain
layerwise streaming.  Packed int8 storage (not jnp.int4): the same bytes
on every backend.
(reference quantization story: diffusion/quantization/{base,fp8}.py,
docs/user_guide/diffusion_acceleration.md:19,46)"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from vllm_omni_tpu.config.diffusion import OmniDiffusionConfig
from vllm_omni_tpu.diffusion.engine import DiffusionEngine
from vllm_omni_tpu.diffusion.quantization import (
    quantize_linear_weight_int4,
    quantize_params,
    quantize_params_host,
    unpack_int4,
)
from vllm_omni_tpu.diffusion.request import (
    OmniDiffusionRequest,
    OmniDiffusionSamplingParams,
)
from vllm_omni_tpu.models.common import nn


@pytest.mark.parametrize("in_dim", [16, 37])  # even + odd (pad row)
def test_pack_unpack_roundtrip(in_dim):
    w = np.asarray(
        jax.random.normal(jax.random.PRNGKey(0), (in_dim, 24)) * 0.3)
    q = quantize_linear_weight_int4(jnp.asarray(w))
    assert q["w_q4"].shape == ((in_dim + 1) // 2, 24)
    assert q["w_q4"].dtype == jnp.int8
    deq = np.asarray(
        unpack_int4(q["w_q4"], in_dim, jnp.float32) * q["w_scale"])
    # absmax scaling to [-8, 7]: error bounded by half an LSB per channel
    scale = np.asarray(q["w_scale"])
    assert (np.abs(deq - w) <= scale[None, :] * 0.5 + 1e-7).all()


def test_unpack_restores_row_order():
    """Row 2i packs into the low nibble, 2i+1 into the high nibble; the
    unpack interleave must restore the exact original order (a swap
    would silently transpose half the weight rows)."""
    w = np.zeros((6, 2), np.float32)
    w[:, 0] = [1, 2, 3, 4, 5, 6]
    w[:, 1] = [-1, -2, -3, -4, -5, -6]
    q = quantize_linear_weight_int4(jnp.asarray(w))
    deq = np.asarray(
        unpack_int4(q["w_q4"], 6, jnp.float32) * q["w_scale"])
    assert np.argmax(deq[:, 0]) == 5
    assert np.argmin(np.abs(deq[:, 0])) == 0
    # strictly increasing column 0, decreasing column 1
    assert (np.diff(deq[:, 0]) > 0).all()
    assert (np.diff(deq[:, 1]) < 0).all()


def test_host_int4_bit_identical_to_device():
    w = np.asarray(
        jax.random.normal(jax.random.PRNGKey(1), (96, 48)) * 0.07)
    dev = quantize_params({"w": jnp.asarray(w)}, mode="int4")
    host = quantize_params_host({"w": w}, mode="int4")
    np.testing.assert_array_equal(
        np.asarray(dev["w_q4"]), np.asarray(host["w_q4"]))
    np.testing.assert_array_equal(
        np.asarray(dev["w_scale"]), np.asarray(host["w_scale"]))


def test_linear_consumes_packed_weights():
    w = np.asarray(
        jax.random.normal(jax.random.PRNGKey(2), (64, 32)) * 0.1)
    q = quantize_params(
        {"w": jnp.asarray(w), "b": jnp.ones((32,))}, mode="int4")
    x = jnp.asarray(np.random.default_rng(0).normal(size=(4, 64)),
                    jnp.float32)
    y = nn.linear(q, x)
    deq = unpack_int4(q["w_q4"], 64, jnp.float32) * q["w_scale"]
    np.testing.assert_allclose(
        np.asarray(y), np.asarray(x @ deq + 1.0), rtol=1e-5, atol=1e-5)


def test_quantize_init_blockwise_matches_post_hoc_structure():
    """quantize_init='int4' (blockwise init+quantize — the path that
    never materializes the float tree) must produce the same tree
    structure the post-hoc quantizer does: every 2-D linear packed,
    norms untouched."""
    from vllm_omni_tpu.models.qwen_image.pipeline import (
        QwenImagePipeline,
        QwenImagePipelineConfig,
    )

    pipe = QwenImagePipeline(QwenImagePipelineConfig.tiny(),
                             dtype=jnp.float32, quantize_init="int4")
    blk = pipe.dit_params["blocks_stacked"]  # leading layer axis
    assert "w_q4" in blk["to_q"] and "w" not in blk["to_q"]
    assert blk["to_q"]["w_q4"].dtype == jnp.int8
    assert "w" in blk["norm_q"]  # 1-D rmsnorm passes through
    assert "w_q4" in pipe.dit_params["proj_out"]
    assert blk["to_q"]["w_q4"].shape[0] == pipe.cfg.dit.num_layers


def test_engine_int4_e2e_close_to_dense():
    """Engine-level: quantization='int4' routes through quantize_init
    and generates an image close to the dense one (int4 perturbs, it
    must not scramble)."""
    def gen(quant):
        eng = DiffusionEngine(OmniDiffusionConfig(
            model="qi-tiny", model_arch="QwenImagePipeline",
            dtype="float32", extra={"size": "tiny"}, quantization=quant,
            default_height=32, default_width=32,
        ), warmup=False)
        if quant:
            assert "w_q4" in \
                eng.pipeline.dit_params["blocks_stacked"]["to_q"]
        sp = OmniDiffusionSamplingParams(
            height=32, width=32, num_inference_steps=3,
            guidance_scale=4.0, seed=7)
        return eng.step(OmniDiffusionRequest(
            prompt=["a red cube"], sampling_params=sp,
            request_ids=["a"]))[0].data

    base = gen("")
    q = gen("int4")
    assert q.shape == (32, 32, 3)
    diff = np.abs(base.astype(np.int32) - q.astype(np.int32))
    assert diff.mean() < 24.0, diff.mean()


def test_stacked_scan_matches_unrolled():
    """dit.forward walks blocks_stacked with lax.scan (the layout
    quantize_init emits — one block's HLO instead of L copies).  Same
    quantized weights stacked vs listed must produce the identical
    image: scan is a program-size optimization, not a math change."""
    from vllm_omni_tpu.models.qwen_image.pipeline import (
        QwenImagePipeline,
        QwenImagePipelineConfig,
    )

    cfg = QwenImagePipelineConfig.tiny()
    dense = QwenImagePipeline(cfg, dtype=jnp.float32, seed=0)
    q = quantize_params(dense.dit_params, mode="int4")

    unrolled = QwenImagePipeline(cfg, dtype=jnp.float32, seed=0,
                                 init_weights=False)
    unrolled.dit_params = q
    unrolled.text_params = dense.text_params
    unrolled.vae_params = dense.vae_params

    stacked = QwenImagePipeline(cfg, dtype=jnp.float32, seed=0,
                                init_weights=False)
    stacked.dit_params = {
        **{k: v for k, v in q.items() if k != "blocks"},
        "blocks_stacked": jax.tree.map(
            lambda *xs: jnp.stack(xs), *q["blocks"]),
    }
    stacked.text_params = dense.text_params
    stacked.vae_params = dense.vae_params

    sp = OmniDiffusionSamplingParams(
        height=32, width=32, num_inference_steps=3, guidance_scale=4.0,
        seed=7)

    def gen(pipe):
        req = OmniDiffusionRequest(
            prompt=["a red cube"], sampling_params=sp,
            request_ids=["a"])
        return pipe.forward(req)[0].data

    np.testing.assert_array_equal(gen(stacked), gen(unrolled))


@pytest.mark.parametrize("chunk", [1, 3])
def test_host_step_loop_matches_device_loop(chunk):
    """step_loop='host' re-invokes the compiled denoise executable with
    num_steps=k on a schedule rolled to the chunk start (the host
    regains control between chunks).  chunk=3 over 4 steps also
    exercises the final partial chunk.  Identical math to the device
    fori_loop: images must match exactly."""
    from vllm_omni_tpu.models.qwen_image.pipeline import (
        QwenImagePipeline,
        QwenImagePipelineConfig,
    )

    cfg = QwenImagePipelineConfig.tiny()
    dev = QwenImagePipeline(cfg, dtype=jnp.float32, seed=0)
    host = QwenImagePipeline(cfg, dtype=jnp.float32, seed=0,
                             init_weights=False, step_loop="host",
                             step_chunk=chunk)
    host.dit_params = dev.dit_params
    host.text_params = dev.text_params
    host.vae_params = dev.vae_params

    sp = OmniDiffusionSamplingParams(
        height=32, width=32, num_inference_steps=4, guidance_scale=4.0,
        seed=7)

    def gen(pipe):
        req = OmniDiffusionRequest(
            prompt=["a red cube"], sampling_params=sp,
            request_ids=["a"])
        return pipe.forward(req)[0].data

    np.testing.assert_array_equal(gen(host), gen(dev))


@pytest.mark.parametrize("backend,extra", [
    ("teacache", {"rel_l1_threshold": 1e9}),     # drift gate always skips
    ("taylorseer", {"rel_l1_threshold": 1e9}),
    ("teacache", {"scm_steps_mask": [True, True, False, True, False,
                                     True]}),    # deterministic mask
])
def test_host_step_loop_cache_matches_device_loop(backend, extra):
    """Step caches under the chunked host loop: the cache carry threads
    through the device-call boundaries and skip decisions use the GLOBAL
    step index, so skips and pixels are identical to the uninterrupted
    device fori_loop.  chunk=2 over 6 steps crosses two chunk
    boundaries with skip state live."""
    from vllm_omni_tpu.diffusion.cache import StepCacheConfig
    from vllm_omni_tpu.models.qwen_image.pipeline import (
        QwenImagePipeline,
        QwenImagePipelineConfig,
    )

    cfg = QwenImagePipelineConfig.tiny()
    cc = StepCacheConfig.from_dict(backend, dict(extra))
    dev = QwenImagePipeline(cfg, dtype=jnp.float32, seed=0,
                            cache_config=cc)
    host = QwenImagePipeline(cfg, dtype=jnp.float32, seed=0,
                             init_weights=False, step_loop="host",
                             step_chunk=2, cache_config=cc)
    host.dit_params = dev.dit_params
    host.text_params = dev.text_params
    host.vae_params = dev.vae_params

    sp = OmniDiffusionSamplingParams(
        height=32, width=32, num_inference_steps=6, guidance_scale=4.0,
        seed=7)

    def gen(pipe):
        req = OmniDiffusionRequest(
            prompt=["a red cube"], sampling_params=sp,
            request_ids=["a"])
        out = pipe.forward(req)[0].data
        return out, pipe.last_skipped_steps

    img_dev, skipped_dev = gen(dev)
    img_host, skipped_host = gen(host)
    assert skipped_dev > 0, "cache never fired — test proves nothing"
    assert skipped_host == skipped_dev
    np.testing.assert_array_equal(img_host, img_dev)


def test_real_q_preset_is_full_depth():
    """The bench preset that makes the 60-layer number a measurement:
    real DiT geometry end to end (reference transformer config.json —
    60 layers / 24 heads / joint 3584)."""
    from vllm_omni_tpu.models.qwen_image.pipeline import (
        QwenImagePipelineConfig,
    )

    cfg = QwenImagePipelineConfig.real_q()
    real = QwenImagePipelineConfig.real()
    assert cfg.dit == real.dit  # full 60-layer geometry, not a stand-in
    assert cfg.text.hidden_size == real.text.hidden_size  # joint width
