"""The names the program gives its own work in the profiler's trace
(docs/observability.md): ``jax.named_scope`` on the device's work,
``TraceAnnotation`` on the engine thread's phases, and the two small
records that ride the step (context sums in the flight record, the
``intake_ms`` histogram).  All on the CPU, at a tiny width."""

import glob
import os
import re
import time

import jax
import jax.numpy as jnp
import pytest

from vllm_omni_tpu.engine import EngineConfig, LLMEngine
from vllm_omni_tpu.models.common import transformer as tfm
from vllm_omni_tpu.sampling_params import SamplingParams

DEVICE_SCOPES = ("omni.embed", "omni.proj", "omni.kv_write", "omni.attn",
                 "omni.moe", "omni.lm_head", "omni.sampler", "omni.logprobs")


def _engine(cfg=None, **kw):
    cfg = cfg or tfm.TransformerConfig.tiny(vocab_size=64)
    params = tfm.init_params(jax.random.PRNGKey(0), cfg, jnp.float32)
    return LLMEngine(params, cfg, EngineConfig(
        num_pages=64, page_size=4, max_model_len=128, max_num_seqs=4,
        max_num_batched_tokens=64, dtype=jnp.float32, **kw))


# ------------------------------------------------------------ device scopes
@pytest.mark.parametrize("which", ["_decode_lp_fn", "_unified_fn"])
def test_lowered_executables_carry_every_scope(which):
    """Scopes are metadata: the lowered text of the decode (with
    logprobs) and of the unified executable names every group of device
    work, and the step runs as before."""
    engine = _engine(tfm.TransformerConfig.tiny_moe(vocab_size=64))
    lowered = []
    orig = getattr(engine.runner, which)

    def spy(*args, **kwargs):
        lowered.append(orig.lower(*args, **kwargs).as_text(debug_info=True))
        return orig(*args, **kwargs)

    setattr(engine.runner, which, spy)
    (out,) = engine.generate([[1, 2, 3, 4, 5]], SamplingParams(
        temperature=0.0, max_tokens=3, ignore_eos=True, logprobs=0))
    assert len(out.outputs[0].token_ids) == 3
    assert lowered, f"{which} never ran"
    for scope in DEVICE_SCOPES:
        # a nested jit (the sampler's) is lowered as a function of its
        # own, whose scope paths start at the scope
        assert re.search(rf'["/]{re.escape(scope)}["/]', lowered[0]), \
            f"{which}: no operation under {scope}"


# -------------------------------------------------------------- host phases
def test_engine_thread_phases_land_in_the_profilers_trace(tmp_path):
    """A tiny pipeline stepped under start_profile/stop_profile leaves an
    ``.xplane.pb`` whose engine-thread line holds ``omni.step`` with the
    step's phases inside it; stop_profile says where the trace landed, and
    a second start/stop in the same process works."""
    from benchmark import span_readers
    from vllm_omni_tpu.config.stage import StageConfig
    from vllm_omni_tpu.entrypoints.omni import Omni

    omni = Omni(stage_configs=[StageConfig(
        stage_id=0, stage_type="llm",
        engine_args={"model_factory": "tests.helpers:tiny_lm_factory",
                     "num_pages": 64, "page_size": 4, "max_model_len": 128},
        engine_input_source=[-1], final_output=True,
        final_output_type="text",
        default_sampling_params={"temperature": 0.0, "max_tokens": 4})])
    omni.generate([[1, 2, 3]])  # compile outside the trace
    landed = []
    for n in (1, 2):
        omni.start_profile(str(tmp_path / f"t{n}"))
        omni.generate([[1, 2, 3], [4, 5, 6, 7]])
        landed.append(omni.stop_profile())
        assert landed[-1] == os.path.join(str(tmp_path / f"t{n}"), "stage_0")
        assert glob.glob(os.path.join(landed[-1], "**", "*.xplane.pb"),
                         recursive=True)
    (path,) = glob.glob(os.path.join(landed[0], "**", "*.xplane.pb"),
                        recursive=True)
    _, phases = span_readers.read_planes(path)
    steps = [p for p in phases if p[2] == "omni.step"]
    assert len(steps) >= 4  # one prefill step and three decode steps
    for want in ("omni.schedule", "omni.assemble", "omni.dispatch",
                 "omni.readback", "omni.update", "omni.record"):
        inside = [p for p in phases if p[2] == want and any(
            s[0] <= p[0] and p[1] <= s[1] for s in steps)]
        assert len(inside) >= len(steps), want
    # in time order inside one step
    s0, s1, _ = steps[0]
    first = {name: start for start, end, name in sorted(phases, reverse=True)
             if s0 <= start and end <= s1}
    order = ["omni.schedule", "omni.assemble", "omni.dispatch",
             "omni.readback", "omni.update"]
    assert [first[n] for n in order] == sorted(first[n] for n in order)


# ---------------------------------------------------------- the two records
def test_flight_record_carries_the_steps_context_sums():
    engine = _engine()
    sp = SamplingParams(temperature=0.0, max_tokens=3, ignore_eos=True)
    engine.add_request([1, 2, 3, 4, 5], sp)
    engine.add_request([6, 7, 8], sp)
    engine.step()
    engine.step()
    first, second = engine.flight.tail(2)
    # prefill: 5 tokens from 0 attend 1+2+3+4+5 positions, 3 tokens 1+2+3
    assert (first["prefill_tokens"], first["prefill_ctx"],
            first["decode_ctx"]) == (8, 15 + 6, 0)
    # decode: one token at position 5 attends 6, one at position 3 attends 4
    assert (second["decodes"], second["prefill_ctx"],
            second["decode_ctx"]) == (2, 0, 6 + 4)
    assert all(isinstance(r[k], int) for r in (first, second)
               for k in ("prefill_ctx", "decode_ctx"))


def test_intake_ms_counts_the_stamped_requests_only():
    engine = _engine()
    sp = SamplingParams(temperature=0.0, max_tokens=1)
    engine.add_request([1, 2, 3], sp)
    engine.add_request([1, 2, 3], sp, additional_information={"tenant": "a"})
    assert engine.metrics_snapshot()["intake_ms"]["count"] == 0
    stamp = time.monotonic() - 0.25
    engine.add_request([4, 5, 6], sp,
                       additional_information={"accept_mono": stamp})
    engine.add_request([7, 8, 9], sp,
                       additional_information={"accept_mono": stamp})
    got = engine.metrics_snapshot()["intake_ms"]
    assert got["count"] == 2
    assert 250.0 <= got["sum"] / got["count"] < 2000.0
    from vllm_omni_tpu.metrics import prometheus

    text = prometheus.render_exposition({}, {0: engine.metrics_snapshot()})
    assert 'intake_ms_count{stage="0"} 2' in text
    assert prometheus.validate_exposition(text) == []
