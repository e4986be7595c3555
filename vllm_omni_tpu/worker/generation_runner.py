"""One-shot generation model runner.

TPU-native counterpart of the reference's GPUGenerationModelRunner
(reference: worker/gpu_generation_model_runner.py:44 — no sampler;
``_run_generation_model`` returns waveform/image tensors :408-447).  Paired
with ``GenerationScheduler``: every request arrives as a single full-prompt
prefill and finishes in one step; the model's forward output (not sampled
tokens) is the result, stored into ``request.multimodal_output``.

Model protocol (duck-typed):
- ``forward(params, token_ids [B, S], lengths [B]) -> dict[str, jax.Array]``
  batched over padded inputs; jit-compatible.
- ``slice_output(outputs, row, in_len) -> dict[str, np.ndarray]``
  extract one request's result from the padded batch.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from vllm_omni_tpu.core.scheduler import SchedulerOutput
from vllm_omni_tpu.introspection.phase_clock import CompileStats, PhaseClock
from vllm_omni_tpu.worker.model_runner import (
    RunnerOutput,
    _bucket,
    _bucketed_prefill_shapes,
    _make_buckets,
)


class GenerationModelRunner:
    def __init__(self, params, model, max_num_seqs: int = 8,
                 max_model_len: int = 4096):
        self.params = params
        self.model = model
        self._batch_buckets = _make_buckets(1, max(max_num_seqs, 1))
        self._seq_buckets = _make_buckets(16, max(max_model_len, 16))
        self._forward = jax.jit(model.forward)
        # the engine replaces it with its own clock (ARModelRunner)
        self.phases = PhaseClock()
        # the warm-up's executables by name, as ARModelRunner keeps them
        self.compile_stats = CompileStats()

    def precompile(self, prefill_shapes=(), progress_fn=None) -> int:
        """Warm the cond-free padded-batch forward for declared
        (batch, seq_len) shapes (same motivation as
        ARModelRunner.precompile: a shape-cache miss mid-traffic stalls
        in-flight requests for a full XLA compile).  Conditioning
        models run this same 3-arg executable whenever
        ``batch_conditioning`` returns None (an all-unconditioned
        batch), so it is warmed for them too; only the conditioned
        4-arg specialization depends on the per-request conditioning
        pytree and cannot be warmed generically."""
        built = 0
        for b, s in _bucketed_prefill_shapes(
                prefill_shapes, self._batch_buckets, self._seq_buckets):
            if progress_fn is not None:
                progress_fn(f"precompile generation b={b} s={s}")
            with self.compile_stats.warming_up(), \
                    self.compile_stats.first_call(
                        self.phases, ("generation", b, s)) as p:
                out = self._forward(
                    self.params, jnp.zeros((b, s), jnp.int32),
                    jnp.full((b,), s, jnp.int32))
                jax.block_until_ready(out)
                p.waited()
            built += 1
        return built

    def execute(self, sched_out: SchedulerOutput,
                extract_kv: bool = True) -> RunnerOutput:
        out = RunnerOutput()
        scheds = sched_out.prefills
        if not scheds:
            return out
        b = _bucket(len(scheds), self._batch_buckets)
        s_len = _bucket(max(s.num_new_tokens for s in scheds),
                        self._seq_buckets)
        token_ids = np.zeros((b, s_len), np.int32)
        lengths = np.zeros((b,), np.int32)
        for i, sc in enumerate(scheds):
            n = sc.num_new_tokens
            token_ids[i, :n] = sc.request.prompt_token_ids[:n]
            lengths[i] = n
        # optional conditioning extension: models exposing
        # ``batch_conditioning(requests, batch) -> pytree`` take it as a
        # fourth forward argument (per-request voice vectors etc.);
        # jax.jit specializes per call signature, so the cond-free path
        # keeps its own cached executable
        cond = None
        if hasattr(self.model, "batch_conditioning"):
            cond = self.model.batch_conditioning(
                [sc.request for sc in scheds], b)
        # the launch and the one transfer back are what this step waits
        # on the device (``device_ms`` of the step's record)
        with self.phases.phase("omni.dispatch") as p:
            if cond is not None:
                outputs = self._forward(
                    self.params, jnp.asarray(token_ids),
                    jnp.asarray(lengths), cond)
            else:
                outputs = self._forward(
                    self.params, jnp.asarray(token_ids),
                    jnp.asarray(lengths))
            p.waited()
        with self.phases.phase("omni.readback") as p:
            # one pytree transfer, not a sync per output key (first
            # omnilint OL2 harvest)
            # omnilint: disable=OL2 - single batched sync per one-shot batch
            outputs = jax.device_get(outputs)
            p.waited()
            outputs = {k: np.asarray(v) for k, v in outputs.items()}
        for i, sc in enumerate(scheds):
            sc.request.multimodal_output.update(
                self.model.slice_output(outputs, i, int(lengths[i]))
            )
        return out

    def extract_kv(self, block_ids, seq_len):
        raise NotImplementedError("generation models have no KV cache")
