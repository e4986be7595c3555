"""Deepstack visual-feature injection into early LM layers.

Reference semantics (qwen3_omni_moe_thinker.py:177-178): after decoder
layer i (for i < n_deep), the multiscale visual features of level i are
added to the residual stream at visual-token positions.  Here the
processor ships a dense [n_deep, S, hidden] table (zeros at non-visual
rows) and the served forward adds level i after layer i.
"""

import numpy as np

import jax
import jax.numpy as jnp

from tests.helpers import unified_step
from vllm_omni_tpu.models.common import transformer as tfm
from vllm_omni_tpu.ops.paged_attention import init_kv_cache


def _setup(n_layers=3, seed=0):
    cfg = tfm.TransformerConfig(
        vocab_size=64, hidden_size=32, num_layers=n_layers, num_heads=2,
        num_kv_heads=2, head_dim=16, intermediate_size=64,
    )
    params = tfm.init_params(jax.random.PRNGKey(seed), cfg, jnp.float32)
    return cfg, params


def _caches(cfg, num_pages, page):
    return init_kv_cache(cfg.num_layers, num_pages, page, cfg.num_kv_heads,
                         cfg.head_dim, jnp.float32)


def test_zero_deepstack_is_identity():
    cfg, params = _setup()
    b, s, page = 2, 8, 8
    toks = np.arange(b * s, dtype=np.int32).reshape(b, s) % 60
    chunks = [(toks[i], 0, [i]) for i in range(b)]
    base, _ = unified_step(params, cfg, _caches(cfg, 4, page), chunks, page)
    zeros = [np.zeros((2, s, cfg.hidden_size), np.float32)] * b
    same, _ = unified_step(params, cfg, _caches(cfg, 4, page), chunks, page,
                           deepstack=zeros)
    np.testing.assert_allclose(np.asarray(jnp.stack(base)),
                               np.asarray(jnp.stack(same)), atol=1e-6)


def test_injection_changes_only_causal_futures():
    """A deepstack perturbation at position p changes outputs at
    positions >= p (causal flow) and leaves positions < p untouched."""
    cfg, params = _setup()
    s, page = 8, 8
    toks = np.arange(s, dtype=np.int32) % 60

    def run(deep):
        (h,), _ = unified_step(params, cfg, _caches(cfg, 2, page),
                               [(toks, 0, [0])], page, deepstack=[deep])
        return np.asarray(h)

    p = 4
    deep = np.zeros((2, s, cfg.hidden_size), np.float32)
    base = run(deep)
    deep[0, p] = 1.0
    pert = run(deep)
    assert np.allclose(base[:p], pert[:p], atol=1e-6)
    assert not np.allclose(base[p:], pert[p:], atol=1e-4)


def test_chunked_prefill_matches_oneshot():
    """Two-chunk prefill with sliced deepstack rows reproduces the
    one-shot forward — the runner slices the request-level table by
    chunk the same way."""
    cfg, params = _setup()
    s, page = 8, 4
    toks = np.arange(s, dtype=np.int32) % 60
    pages = list(range(s // page))
    rng = np.random.default_rng(0)
    deep = rng.normal(size=(2, s, cfg.hidden_size)).astype(np.float32)

    (full,), _ = unified_step(params, cfg, _caches(cfg, 4, page),
                              [(toks, 0, pages)], page, deepstack=[deep])

    half = s // 2
    (h1,), caches = unified_step(
        params, cfg, _caches(cfg, 4, page), [(toks[:half], 0, pages)], page,
        deepstack=[deep[:, :half]])
    (h2,), _ = unified_step(
        params, cfg, caches, [(toks[half:], half, pages)], page,
        deepstack=[deep[:, half:]])
    np.testing.assert_allclose(np.asarray(full[half:]), np.asarray(h2),
                               atol=1e-4)
    np.testing.assert_allclose(np.asarray(full[:half]), np.asarray(h1),
                               atol=1e-5)


def test_engine_e2e_deepstack_conditions_output():
    """The tiny Qwen3 ViT tower emits deepstack features; they must reach
    the LM — zeroing them changes the generated tokens; and the chunked
    engine path produces the same tokens as the one-shot path."""
    from vllm_omni_tpu.engine import EngineConfig, LLMEngine
    from vllm_omni_tpu.models.common.transformer import (
        TransformerConfig,
        init_params,
    )
    from vllm_omni_tpu.models.qwen3_omni import real_multimodal as rm
    from vllm_omni_tpu.sampling_params import SamplingParams

    cfg = TransformerConfig.tiny(vocab_size=64)
    params = init_params(jax.random.PRNGKey(0), cfg, jnp.float32)
    proc = rm.build_tiny_processor(params, cfg)
    rng = np.random.default_rng(0)
    img = (rng.uniform(0, 255, (64, 64, 3))).astype(np.uint8)
    out = proc([1, 2, 3], {"image": [img]})
    assert out.deepstack_embeds is not None
    # one sparse span per visual item, covering exactly the image tokens
    (off, arr), = out.deepstack_embeds
    n_img = len(out.prompt_token_ids) - 3
    assert off == 0 and arr.shape[1] == n_img
    assert np.abs(arr).sum() > 0

    def gen(deepstack, chunked=False):
        ecfg = EngineConfig(
            max_model_len=128, num_pages=32, page_size=16,
            enable_chunked_prefill=chunked,
            max_num_batched_tokens=8 if chunked else 2048,
            dtype=jnp.float32, seed=7,
        )
        eng = LLMEngine(params, cfg, ecfg)
        eng.add_request(
            out.prompt_token_ids, SamplingParams(max_tokens=8,
                                                 temperature=0.0),
            request_id="r0", prompt_embeds=out.prompt_embeds,
            mrope_positions=out.mrope_positions,
            mrope_delta=out.mrope_delta,
            deepstack_embeds=deepstack,
        )
        fin = []
        while eng.has_unfinished_requests:
            fin.extend(eng.step())
        return fin[0].outputs[0].token_ids

    # amplified features guarantee a greedy-token flip (the tiny random
    # tower's raw magnitudes are too small to move argmax reliably)
    loud = [(off, arr * 100.0) for off, arr in out.deepstack_embeds]
    with_ds = gen(loud)
    without = gen(None)
    assert with_ds != without, (
        "deepstack features did not reach the LM forward")
    assert gen(loud, chunked=True) == with_ds
