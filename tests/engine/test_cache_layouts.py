"""The one table of what a cache layout cannot carry
(``vllm_omni_tpu/kvcache/layouts.py``): for every tiny model and every
mechanism its answer is the expected one, and the engine and the runner
refuse from the same text."""

import functools
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from vllm_omni_tpu.engine.llm_engine import EngineConfig, LLMEngine
from vllm_omni_tpu.kvcache import layouts
from vllm_omni_tpu.models.common import transformer as tfm
from vllm_omni_tpu.worker.model_runner import ARModelRunner

#: model -> (its tiny configuration, the layouts its cache has)
MODELS = {
    "tiny_moe": (None, ()),
    "glm4_moe_lite": ("glm4_moe_lite", ("latent row",)),
    "qwen3_next": ("qwen3_next", ("recurrent state",)),
    "dots3_note": ("dots3_note", ("window pages", "latent row")),
    "lfm2_moe": ("lfm2_moe", ("recurrent state",)),
    "granite_moe_hybrid": ("granite_moe_hybrid", ("recurrent state",)),
    "mimo_v2": ("mimo_v2", ("window pages", "pools of two shapes")),
}
#: mechanism -> an engine configuration that asks for it
ASKS = {
    layouts.INT8: dict(kv_cache_dtype="int8"),
    layouts.TRANSFER: dict(engine_role="prefill"),
    layouts.OFFLOAD: dict(kv_offload=True, kv_offload_policy="always"),
    layouts.SPECULATIVE: dict(num_speculative_tokens=2),
    layouts.TENSOR_PARALLEL: dict(tensor_parallel_size=2),
}
RUNNER = dict(num_pages=8, page_size=16, max_model_len=64,
              dtype=jnp.float32, max_num_seqs=2, max_num_batched_tokens=32,
              num_window_pages=8)


def _cfg(model: str) -> tfm.TransformerConfig:
    module = MODELS[model][0]
    if module is None:
        return tfm.TransformerConfig.tiny_moe()
    return importlib.import_module(
        f"vllm_omni_tpu.models.{module}").tiny_config()


@functools.lru_cache(maxsize=None)
def _runner(model: str) -> ARModelRunner:
    cfg = _cfg(model)
    return ARModelRunner(
        tfm.init_params(jax.random.PRNGKey(0), cfg), cfg, **RUNNER)


def test_the_table_has_a_reason_in_every_cell():
    assert set(ASKS) == set(layouts.MECHANISMS)
    for name, layout in layouts.LAYOUTS.items():
        assert layout.keeps, name
        assert set(layout.reasons) == set(layouts.MECHANISMS), name
        assert all(layout.reasons.values()), name


@pytest.mark.parametrize("mechanism", layouts.MECHANISMS)
@pytest.mark.parametrize("model", sorted(MODELS))
def test_engine_and_runner_refuse_from_the_table(model, mechanism):
    cfg, has = _cfg(model), MODELS[model][1]
    assert layouts.layouts_of(cfg) == has
    reason = layouts.cannot_carry(cfg)[mechanism]
    if not has:
        # per-head (K, V) pages carry all five
        assert reason is None and layouts.refusal(cfg, [mechanism]) is None
        return
    assert all(layouts.LAYOUTS[name].reasons[mechanism] in reason
               for name in has)

    # the engine: one ValueError before anything is built (no weights are
    # needed to get there), naming the layouts and the mechanism's reason
    with pytest.raises(ValueError) as refused:
        LLMEngine(None, cfg, EngineConfig(**ASKS[mechanism]))
    assert f"{mechanism} ({reason})" in str(refused.value)
    assert all(layouts.LAYOUTS[name].keeps in str(refused.value)
               for name in has)

    # the runner's own entry points, from the same text
    if mechanism == layouts.INT8:
        with pytest.raises(ValueError) as refused:
            ARModelRunner(None, cfg, **RUNNER, kv_cache_dtype="int8")
    elif mechanism == layouts.TENSOR_PARALLEL:
        from jax.sharding import Mesh
        with pytest.raises(ValueError) as refused:
            ARModelRunner(None, cfg, **RUNNER, mesh=Mesh(
                np.array(jax.devices()[:2]), ("tp",)))
    elif mechanism == layouts.SPECULATIVE:
        with pytest.raises(ValueError) as refused:
            _runner(model).set_draft_fn(lambda *a, **k: None, 2)
    elif mechanism == layouts.TRANSFER:
        with pytest.raises(NotImplementedError) as refused:
            _runner(model).extract_kv([0], 4)
        assert reason in str(refused.value)
        with pytest.raises(NotImplementedError) as refused:
            _runner(model).inject_kv([0], [])
    else:
        # parking and tiered offload reach the runner through extract_kv
        # and inject_kv: it has no entry point of their own
        return
    assert reason in str(refused.value)


def test_every_asked_mechanism_is_named_in_one_refusal():
    cfg = _cfg("dots3_note")
    asked = dict(kv_cache_dtype="int8", tensor_parallel_size=2,
                 num_speculative_tokens=2)
    with pytest.raises(ValueError) as refused:
        LLMEngine(None, cfg, EngineConfig(**asked))
    reasons = layouts.cannot_carry(cfg)
    for mechanism in (layouts.INT8, layouts.TENSOR_PARALLEL,
                      layouts.SPECULATIVE):
        assert f"{mechanism} ({reasons[mechanism]})" in str(refused.value)
    assert layouts.OFFLOAD not in str(refused.value)
