"""Shared test fixtures: tiny random-weight model factories (the analogue
of the reference's random-weight HF checkpoints, SURVEY.md §4)."""

import jax
import jax.numpy as jnp

from vllm_omni_tpu.models.common import transformer as tfm


def tiny_lm_factory():
    """model_factory hook for llm stages: (params, cfg, eos_token_id)."""
    cfg = tfm.TransformerConfig.tiny(vocab_size=64)
    params = tfm.init_params(jax.random.PRNGKey(0), cfg, jnp.float32)
    return params, cfg, None


def forward_tokens_and_kv(config, upstream_outputs):
    """custom_process_input_func: next stage re-decodes the upstream
    prompt+output with the upstream's KV prefix injected (same-model KV
    reuse across a stage boundary)."""
    from vllm_omni_tpu.entrypoints.omni_stage import StageRequest

    reqs = []
    for out in upstream_outputs:
        info = {}
        kv = out.multimodal_output.get("kv_payload")
        if kv is not None:
            info["kv_payload"] = kv
        reqs.append(StageRequest(
            request_id=out.request_id,
            prompt_token_ids=(list(out.prompt_token_ids)
                              + list(out.outputs[0].token_ids)),
            additional_information=info,
        ))
    return reqs


def unified_step(params, cfg, kv_caches, chunks, page_size, deepstack=None):
    """One call of the served forward (``tfm.forward_unified``) over
    ``chunks``: a list of ``(token_ids, start, pages)``, the new tokens of
    one sequence each, the position of the first, and the page ids that
    hold the sequence (earlier chunks' KV is read from them, this chunk's
    is written).  Packs them as the runner does: each chunk at a segment
    aligned to the kernel's token block, ``cu_q_lens`` the segment starts,
    ``q_lens`` the real counts, ``seq_lens`` the context including the
    chunk.  ``deepstack``: for each chunk ``[n_deep, n, hidden]`` or None.

    Returns (for each chunk its hidden rows ``[n, hidden]``, new caches).
    """
    import numpy as np

    from vllm_omni_tpu.ops.ragged_paged_attention import align_to_block

    n_seq = len(chunks)
    starts = np.cumsum([0] + [align_to_block(len(t)) for t, _, _ in chunks])
    total = int(starts[-1])
    token_ids = np.zeros((total,), np.int32)
    positions = np.zeros((total,), np.int32)
    slots = np.full((total,), -1, np.int32)
    tables = np.zeros((n_seq, max(len(p) for _, _, p in chunks)), np.int32)
    deep = None
    if deepstack is not None:
        n_deep = next(d.shape[0] for d in deepstack if d is not None)
        deep = np.zeros((n_deep, total, cfg.hidden_size), np.float32)
    for i, (toks, start, pages) in enumerate(chunks):
        lo, n = int(starts[i]), len(toks)
        pos = start + np.arange(n)
        token_ids[lo:lo + n] = np.asarray(toks)
        positions[lo:lo + n] = pos
        slots[lo:lo + n] = (np.asarray(pages)[pos // page_size] * page_size
                            + pos % page_size)
        tables[i, :len(pages)] = pages
        if deep is not None and deepstack[i] is not None:
            deep[:, lo:lo + n] = np.asarray(deepstack[i])
    q_lens = np.asarray([len(t) for t, _, _ in chunks], np.int32)
    seq_lens = np.asarray([s + len(t) for t, s, _ in chunks], np.int32)
    hidden, caches = tfm.forward_unified(
        params, cfg, jnp.asarray(token_ids), jnp.asarray(positions),
        kv_caches, jnp.asarray(slots), jnp.asarray(tables),
        jnp.asarray(seq_lens), jnp.asarray(starts, jnp.int32),
        jnp.asarray(q_lens), jnp.asarray([n_seq], jnp.int32),
        deepstack=None if deep is None else jnp.asarray(deep))
    return ([hidden[int(starts[i]):int(starts[i]) + int(q_lens[i])]
             for i in range(n_seq)], caches)
