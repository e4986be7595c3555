"""Prefill + paged decode must reproduce the full-sequence forward — the
core numerical contract of the AR engine (what the reference trusts vLLM's
CUDA PagedAttention for)."""

import jax
import jax.numpy as jnp
import numpy as np

from tests.helpers import unified_step
from vllm_omni_tpu.models.common.transformer import (
    TransformerConfig,
    forward_decode,
    forward_hidden,
    init_params,
    logits_from_hidden,
)
from vllm_omni_tpu.ops.paged_attention import init_kv_cache


def test_prefill_matches_full_forward(rng):
    cfg = TransformerConfig.tiny()
    params = init_params(rng, cfg)
    tokens = jax.random.randint(rng, (2, 10), 0, cfg.vocab_size)
    caches = init_kv_cache(
        cfg.num_layers, 16, 4, cfg.num_kv_heads, cfg.head_dim, jnp.float32
    )
    # seq0 -> pages 0..3, seq1 -> pages 8..11
    h_pref, caches = unified_step(
        params, cfg, caches,
        [(np.asarray(tokens[0]), 0, [0, 1, 2, 3]),
         (np.asarray(tokens[1]), 0, [8, 9, 10, 11])], 4)
    h_full = forward_hidden(params, cfg, tokens)
    np.testing.assert_allclose(
        np.asarray(jnp.stack(h_pref)), np.asarray(h_full),
        atol=1e-4, rtol=1e-4
    )


def test_decode_continues_prefill(rng):
    """Greedy-decode 4 tokens with the paged path; check each step's logits
    against re-running the full forward on the growing sequence."""
    cfg = TransformerConfig.tiny()
    params = init_params(rng, cfg)
    b, prompt_len = 2, 7
    tokens = jax.random.randint(rng, (b, prompt_len), 3, cfg.vocab_size)
    page = 4
    caches = init_kv_cache(
        cfg.num_layers, 32, page, cfg.num_kv_heads, cfg.head_dim, jnp.float32
    )
    max_pages = 8
    block_tables = jnp.stack(
        [jnp.arange(max_pages), max_pages + jnp.arange(max_pages)]
    )
    flat_base = block_tables[:, :1] * page  # page0 slot base per seq

    def slot_for(seq, idx):
        p_i, off = idx // page, idx % page
        return int(block_tables[seq, p_i]) * page + off

    h, caches = unified_step(
        params, cfg, caches,
        [(np.asarray(tokens[s]), 0, np.asarray(block_tables[s]))
         for s in range(b)], page)
    logits = logits_from_hidden(params, cfg, jnp.stack(h))[:, -1]
    seqs = np.asarray(tokens)

    for step_i in range(4):
        next_tok = jnp.argmax(logits, axis=-1)
        seqs = np.concatenate([seqs, np.asarray(next_tok)[:, None]], axis=1)
        cur_len = prompt_len + step_i + 1
        dec_slots = jnp.asarray(
            [slot_for(s, cur_len - 1) for s in range(b)]
        )
        h_dec, caches = forward_decode(
            params,
            cfg,
            next_tok,
            jnp.full((b,), cur_len - 1),
            caches,
            dec_slots,
            block_tables,
            jnp.full((b,), cur_len),
        )
        logits = logits_from_hidden(params, cfg, h_dec)
        # oracle: full forward over the whole sequence so far
        h_full = forward_hidden(params, cfg, jnp.asarray(seqs))
        want = logits_from_hidden(params, cfg, h_full)[:, -1]
        np.testing.assert_allclose(
            np.asarray(logits), np.asarray(want), atol=2e-4, rtol=2e-4,
            err_msg=f"decode step {step_i}",
        )
