"""MTP speculative decoding (VERDICT r1 next-step #9; reference: talker
MTP code predictor qwen3_omni_moe_code_predictor_mtp.py + EAGLE propose
gpu_ar_model_runner.py:466-497).

Correctness invariant: spec-decode output is token-identical to plain
greedy decoding — drafts only change HOW MANY steps it takes. The oracle
draft head (drafting with the target model itself) proves the acceptance
path and the step-count win; the random MTP head proves the rejection
path.
"""

import jax
import jax.numpy as jnp
import numpy as np

from vllm_omni_tpu.engine.llm_engine import EngineConfig, LLMEngine
from vllm_omni_tpu.models.common import transformer as tfm
from vllm_omni_tpu.models.qwen3_omni import mtp
from vllm_omni_tpu.sampling_params import SamplingParams


def _mk(params, cfg, draft_fn=None, k=0, **over):
    base = dict(num_pages=64, page_size=4, max_model_len=256,
                max_num_seqs=4, dtype=jnp.float32, seed=0,
                num_speculative_tokens=k)
    base.update(over)
    return LLMEngine(params, cfg, EngineConfig(**base), draft_fn=draft_fn)


def _gen(eng, prompts, sp):
    outs = eng.generate(prompts, sp)
    for o in outs:
        assert not o.is_error, o.error_message
    return [o.outputs[0].token_ids for o in outs]


class OracleDraft:
    """Callable draft_fn drafting with the target model on full context
    (the runner passes ``contexts`` to drafters that accept it) ->
    acceptance is 100%: every verify step should accept all drafts.
    Host-side and slow — test-only."""

    def __init__(self, params, cfg, k):
        self.params, self.cfg, self.k = params, cfg, k

    def __call__(self, last_hidden, last_token, positions, contexts=None):
        b = int(last_hidden.shape[0])
        drafts = np.zeros((b, self.k), np.int32)
        lt = np.asarray(jax.device_get(last_token))
        for i, toks in enumerate(contexts or []):
            toks = list(toks)
            assert toks[-1] == int(lt[i])
            for j in range(self.k):
                h = tfm.forward_hidden(
                    self.params, self.cfg, jnp.asarray([toks]))
                nxt = int(jnp.argmax(tfm.logits_from_hidden(
                    self.params, self.cfg, h[0, -1])))
                drafts[i, j] = nxt
                toks.append(nxt)
        return jnp.asarray(drafts)


def test_spec_decode_random_head_token_identical():
    """Random (untrained) MTP head: drafts mostly rejected, output must
    still be exactly greedy."""
    cfg = tfm.TransformerConfig.tiny()
    params = tfm.init_params(jax.random.PRNGKey(0), cfg, jnp.float32)
    draft_fn = mtp.tiny_factory(params, cfg, 3)
    prompts = [list(np.random.default_rng(i).integers(1, 100, size=7))
               for i in range(3)]
    sp = SamplingParams(temperature=0.0, max_tokens=10)

    want = _gen(_mk(params, cfg), prompts, sp)
    got = _gen(_mk(params, cfg, draft_fn=draft_fn, k=3), prompts, sp)
    assert got == want


def test_spec_decode_oracle_head_accepts_and_saves_steps():
    cfg = tfm.TransformerConfig.tiny()
    params = tfm.init_params(jax.random.PRNGKey(1), cfg, jnp.float32)
    prompt = list(np.random.default_rng(5).integers(1, 100, size=6))
    sp = SamplingParams(temperature=0.0, max_tokens=12)

    plain = _mk(params, cfg)
    want = _gen(plain, [prompt], sp)

    oracle = OracleDraft(params, cfg, 3)
    eng = _mk(params, cfg, draft_fn=oracle, k=3)
    got = _gen(eng, [prompt], sp)
    assert got == want

    stats = eng.runner.spec_stats
    assert stats["verify_steps"] > 0
    # oracle drafts always match: all proposals accepted
    assert stats["accepted"] == stats["proposed"] > 0
    # 12 tokens at up to 4/step: far fewer verify+decode steps than 12
    assert stats["verify_steps"] <= 4


def test_spec_decode_sampled_rejection_acceptance():
    """temperature > 0 requests verify by rejection sampling (reference:
    gpu_ar_model_runner.py:466-497) — with the oracle (greedy-exact)
    draft head the measured acceptance at temperature 0.9 is nonzero,
    and seeded runs are deterministic."""
    cfg = tfm.TransformerConfig.tiny()
    params = tfm.init_params(jax.random.PRNGKey(2), cfg, jnp.float32)
    k = 3
    prompts = [[1, 2, 3], [4, 5, 6]]
    sp = SamplingParams(temperature=0.9, max_tokens=12, seed=11)

    def run():
        eng = _mk(params, cfg, draft_fn=OracleDraft(params, cfg, k), k=k)
        toks = _gen(eng, prompts, sp)
        return toks, dict(eng.runner.spec_stats)

    got, stats = run()
    got2, _ = run()
    assert got == got2  # seeded determinism through the spec path
    assert stats["proposed"] > 0
    assert stats["accepted"] > 0  # nonzero acceptance at T=0.9
    for t in got:
        assert len(t) == 12


def test_on_device_rejection_preserves_target_distribution():
    """The emitted first token of the ON-DEVICE rejection verify
    (sample/sampler.py spec_verify_tokens — the rebuild of the split
    path's host-side accept loop) must be EXACTLY p-distributed (p =
    temperature/top-k/top-p filtered target): accept draft d w.p.
    p(d), else draw from p \\ {d} renormalized.  Empirical check over
    many deterministic (request, step) key streams."""
    from vllm_omni_tpu.sample.sampler import (
        SamplingTensors,
        spec_verify_tokens,
    )

    vocab = 16
    rng = np.random.default_rng(0)
    row = rng.standard_normal(vocab) * 2.0
    temp = 0.9
    p_target = np.asarray(jax.nn.softmax(
        jnp.asarray(row / temp, jnp.float32)), np.float64)
    draft = int(np.argmax(p_target))  # the greedy draft proposal
    s = 256
    logits = jnp.asarray(np.broadcast_to(row, (s, 4, vocab)),
                         jnp.float32)
    drafts = jnp.full((s, 3), draft, jnp.int32)
    n_cand = jnp.full((s,), 4, jnp.int32)
    sp = SamplingParams(temperature=temp, max_tokens=4)
    counts = np.zeros(vocab)
    accepted = proposed = 0
    for step in range(16):
        t = SamplingTensors.build([sp] * s, step=step, base_seed=123,
                                  salts=list(range(s)))
        tk, ct, _ = spec_verify_tokens(logits, drafts, n_cand,
                                       t.temperature, t.top_k, t.top_p,
                                       t.keys)
        tk, ct = np.asarray(tk), np.asarray(ct)
        for i in range(s):
            counts[tk[i, 0]] += 1
        accepted += int((ct - 1).sum())
        proposed += s * 3
        # determinism: the same (seed, salt, step) keys reproduce
        t2 = SamplingTensors.build([sp] * s, step=step, base_seed=123,
                                   salts=list(range(s)))
        tk2, ct2, _ = spec_verify_tokens(logits, drafts, n_cand,
                                         t2.temperature, t2.top_k,
                                         t2.top_p, t2.keys)
        assert np.array_equal(tk, np.asarray(tk2))
        assert np.array_equal(ct, np.asarray(ct2))
    emp = counts / counts.sum()
    tv = 0.5 * np.abs(emp - p_target).sum()
    assert tv < 0.05, (tv, emp, p_target)
    # the greedy-exact draft is accepted at roughly its own probability
    assert accepted > 0.1 * proposed


def test_spec_decode_mixed_batch_greedy_unperturbed():
    """Greedy requests in a mixed batch stay token-identical to plain
    decoding even when sampled requests ride the rejection path."""
    cfg = tfm.TransformerConfig.tiny()
    params = tfm.init_params(jax.random.PRNGKey(2), cfg, jnp.float32)
    draft_fn = mtp.tiny_factory(params, cfg, 2)
    prompts = [[1, 2, 3], [4, 5, 6]]
    sps = [SamplingParams(temperature=0.0, max_tokens=6),
           SamplingParams(temperature=0.8, max_tokens=6, seed=7)]

    want0 = _gen(_mk(params, cfg), [prompts[0]], sps[0])[0]
    eng = _mk(params, cfg, draft_fn=draft_fn, k=2)
    outs = eng.generate(prompts, sps)
    assert outs[0].outputs[0].token_ids == want0
    assert len(outs[1].outputs[0].token_ids) == 6


def test_spec_decode_hidden_chunks_align_with_tokens():
    """collect_hidden + spec decode: the hidden payload must have exactly
    as many rows as plain decoding would emit, even when a stop lands
    inside an accepted run (code-review finding: untrimmed acceptance
    shipped extra rows downstream)."""
    cfg = tfm.TransformerConfig.tiny()
    params = tfm.init_params(jax.random.PRNGKey(3), cfg, jnp.float32)
    prompt = [1, 2, 3, 4]
    plain_eng = _mk(params, cfg, collect_hidden=True)
    plain = plain_eng.generate(
        [prompt], SamplingParams(temperature=0.0, max_tokens=6))[0]
    eos = plain.outputs[0].token_ids[1]

    def run(k, draft_fn):
        eng = LLMEngine(params, cfg, EngineConfig(
            num_pages=64, page_size=4, max_model_len=256,
            dtype=jnp.float32, seed=0, num_speculative_tokens=k,
            collect_hidden=True), eos_token_id=eos, draft_fn=draft_fn)
        out = eng.generate(
            [prompt], SamplingParams(temperature=0.0, max_tokens=6))[0]
        return out

    oracle = OracleDraft(params, cfg, 3)
    want = run(0, None)
    got = run(3, oracle)
    assert got.outputs[0].token_ids == want.outputs[0].token_ids
    assert (got.multimodal_output["hidden_states"].shape
            == want.multimodal_output["hidden_states"].shape)


def test_spec_decode_with_eos_mid_acceptance():
    """A stop token inside the accepted run finishes the request at the
    stop, not after the full accepted list."""
    cfg = tfm.TransformerConfig.tiny()
    params = tfm.init_params(jax.random.PRNGKey(3), cfg, jnp.float32)
    prompt = [1, 2, 3, 4]
    # find the greedy continuation, then pick a later token as eos: the
    # expected output is the prefix through eos's FIRST occurrence
    plain = _gen(_mk(params, cfg), [prompt],
                 SamplingParams(temperature=0.0, max_tokens=6))[0]
    eos = plain[1]
    want = plain[: plain.index(eos) + 1]

    oracle = OracleDraft(params, cfg, 3)
    eng = LLMEngine(params, cfg, EngineConfig(
        num_pages=64, page_size=4, max_model_len=256, dtype=jnp.float32,
        seed=0, num_speculative_tokens=3), eos_token_id=eos,
        draft_fn=oracle)
    got = _gen(eng, [prompt],
               SamplingParams(temperature=0.0, max_tokens=6))[0]
    assert got == want and got[-1] == eos
    # eos arriving inside an accepted draft run must truncate there even
    # when more drafts were accepted by the verify forward
    assert len(got) <= len(plain)
