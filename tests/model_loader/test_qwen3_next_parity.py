"""Qwen3-Next checkpoint loading against the published implementation
(transformers ``Qwen3NextForCausalLM``, its plain torch path) on a tiny
synthetic checkpoint: the tensor names are mapped, the fused
``in_proj_qkvz`` / ``in_proj_ba`` de-interleaved by key head, and the
functional transformer gives the published model's logits."""

import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

torch = pytest.importorskip("torch")
transformers = pytest.importorskip("transformers")

from vllm_omni_tpu.model_loader.hf_qwen import config_from_hf, load_qwen_lm
from vllm_omni_tpu.models import qwen3_next
from vllm_omni_tpu.models.common import transformer as tfm
from vllm_omni_tpu.models.registry import OmniModelRegistry


@pytest.fixture(scope="module")
def checkpoint(tmp_path_factory):
    try:
        from transformers import Qwen3NextConfig, Qwen3NextForCausalLM
    except ImportError:
        pytest.skip("this transformers has no Qwen3-Next")
    torch.manual_seed(0)
    cfg = Qwen3NextConfig(
        vocab_size=96, hidden_size=64, intermediate_size=128,
        num_hidden_layers=4, num_attention_heads=4, num_key_value_heads=2,
        head_dim=32, partial_rotary_factor=0.25, rope_theta=1e7,
        linear_num_key_heads=2, linear_num_value_heads=4,
        linear_key_head_dim=16, linear_value_head_dim=32,
        linear_conv_kernel_dim=4, full_attention_interval=4,
        num_experts=8, num_experts_per_tok=2, moe_intermediate_size=32,
        shared_expert_intermediate_size=32, norm_topk_prob=True,
        decoder_sparse_step=1, mlp_only_layers=[],
        max_position_embeddings=512, tie_word_embeddings=False,
        torch_dtype="float32")
    model = Qwen3NextForCausalLM(cfg).eval()
    with torch.no_grad():
        for name, p in model.named_parameters():
            # the initialiser leaves the norms at 0 / 1 and A_log, dt_bias
            # at values under which a token's decay is ~0: draw all of
            # them, so that every mapped tensor shows in the logits
            if name.endswith(("norm.weight", "layernorm.weight")):
                p.copy_(torch.rand_like(p) * 0.4 - 0.2
                        + (1.0 if "linear_attn.norm" in name else 0.0))
            elif name.endswith("A_log"):
                p.copy_(torch.log(torch.rand_like(p) * 4 + 0.1))
            elif name.endswith("dt_bias"):
                p.copy_(torch.rand_like(p) * 2 - 3)
    path = tmp_path_factory.mktemp("qwen3next")
    model.save_pretrained(path, safe_serialization=True)
    return model, str(path)


def test_config_is_read_from_the_published_keys(checkpoint):
    _, path = checkpoint
    cfg = config_from_hf(path)
    want = dataclasses.replace(qwen3_next.tiny_config(96), rms_eps=cfg.rms_eps)
    assert cfg == want
    assert cfg.layer_types == ("linear_attention",) * 3 + ("full_attention",)
    assert OmniModelRegistry.resolve("Qwen3NextForCausalLM") \
        is qwen3_next.real_factory


def test_loaded_weights_give_the_published_models_logits(checkpoint):
    """70 tokens: past one chunk of the rule (64) and not a multiple of
    it.  float32 on both sides; they differ by summation order through 4
    layers (readings ~1e-5 on logits of order 1)."""
    model, path = checkpoint
    params, cfg, _ = qwen3_next.real_factory(path, dtype="float32")
    ids = np.random.default_rng(0).integers(0, 96, (2, 70))
    with torch.no_grad():
        want = model(torch.tensor(ids)).logits.numpy()
    hidden = tfm.forward_hidden(params, cfg, jnp.asarray(ids, jnp.int32))
    got = np.asarray(tfm.logits_from_hidden(params, cfg, hidden))
    np.testing.assert_allclose(got, want, atol=2e-4, rtol=2e-4)


def test_an_expert_share_loads_its_own_experts_only(checkpoint):
    _, path = checkpoint
    whole, cfg, _ = load_qwen_lm(path, dtype="float32")
    share_cfg = qwen3_next.expert_share(cfg, held=2, offset=4)
    share, _, _ = load_qwen_lm(path, cfg=share_cfg, dtype="float32")
    for a, b in zip(whole["layers"], share["layers"]):
        assert b["router"]["w"].shape[-1] == 8
        for k in ("gate_up", "down"):
            np.testing.assert_array_equal(b["experts"][k],
                                          a["experts"][k][4:6])
