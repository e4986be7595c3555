"""Batched token sampler (greedy / temperature / top-k / top-p).

TPU-native replacement for vLLM's GPU sampler as used by the reference's
AR runner (reference: worker/gpu_ar_model_runner.py:441-444 `_sample`).
Per-request sampling params are vectorized into device arrays so one jitted
function serves any mixed batch — greedy requests ride the same kernel with
temperature 0 handled via argmax selection, avoiding a recompile per
param combination.

Stateless: the caller supplies a fold-in of (seed, step) per request so
resampling a step is deterministic (needed for spec-decode verify later).
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Optional

import functools

import jax
import jax.numpy as jnp
import numpy as np

from vllm_omni_tpu.sampling_params import SamplingParams

_NEG_INF = -1e30


@dataclass
class SamplingTensors:
    temperature: jax.Array  # [B] f32
    top_k: jax.Array        # [B] i32 (0 = off)
    top_p: jax.Array        # [B] f32
    keys: jax.Array         # [B, 2] u32 PRNG keys
    # retained inputs of the key derivation so a cached instance can be
    # re-keyed for a new step without redoing the host-side assembly
    # (pure-decode batches keep the same params for hundreds of steps)
    seeds: Optional[jax.Array] = None   # [B] u32
    salts: Optional[jax.Array] = None   # [B] u32

    def rekey(self, step: int) -> "SamplingTensors":
        """Same batch, new step: only the PRNG keys depend on the step
        index, so a cached instance is reused by swapping keys (one tiny
        fused dispatch instead of rebuilding four arrays)."""
        if self.seeds is None or self.salts is None:
            raise ValueError("rekey needs seeds/salts retained by build()")
        keys = _build_keys(self.seeds, self.salts,
                           jnp.asarray(step, jnp.uint32))
        return dataclasses.replace(self, keys=keys)

    @staticmethod
    def build(
        params: list[SamplingParams],
        step: int,
        base_seed: int = 0,
        salts: Optional[list[int]] = None,
    ):
        """``salts`` (e.g. a stable hash of each request_id) decorrelate
        unseeded requests from each other; explicit per-request seeds remain
        fully deterministic regardless of salt/base_seed."""
        temp = np.array([p.temperature for p in params], np.float32)
        top_k = np.array([p.top_k for p in params], np.int32)
        top_p = np.array([p.top_p for p in params], np.float32)
        if salts is None:
            salts = list(range(len(params)))
        # one vectorized dispatch for the whole batch (per-request PRNGKey/
        # fold_in chains would cost ~4 tiny device ops per row per step)
        seeds = np.array(
            [p.seed if p.seed is not None else base_seed for p in params],
            np.uint32,
        )
        salt_arr = np.array(
            [0 if p.seed is not None else (s & 0x7FFFFFFF)
             for p, s in zip(params, salts)],
            np.uint32,
        )
        seeds_dev = jnp.asarray(seeds)
        salts_dev = jnp.asarray(salt_arr)
        keys = _build_keys(seeds_dev, salts_dev,
                           jnp.asarray(step, jnp.uint32))
        return SamplingTensors(
            temperature=jnp.asarray(temp),
            top_k=jnp.asarray(top_k),
            top_p=jnp.asarray(top_p),
            keys=jnp.asarray(keys),
            seeds=seeds_dev,
            salts=salts_dev,
        )


@jax.jit
@jax.named_scope("omni.sampler")
def _build_keys(seeds: jax.Array, salts: jax.Array, step: jax.Array):
    """[B] seeds + [B] salts + scalar step -> [B, 2] key data, vmapped into
    a single compiled dispatch. Seeded requests pass salt 0 so their stream
    depends only on (seed, step)."""

    def one(seed, salt):
        key = jax.random.fold_in(jax.random.PRNGKey(seed), salt)
        return jax.random.key_data(jax.random.fold_in(key, step))

    return jax.vmap(one)(seeds, salts)


# Candidates a row's thresholds are taken from: the CAP largest scaled
# logits, by one bounded selection.  A constant, not a setting: the
# largest value within 1 ms a step of the cheapest in the chip sweep
# (scripts/sampler_select_sweep.py; the table is in PERF.md, PR 29:
# sample_tokens at [64, 152064] takes 2.4 / 3.3 / 5.1 / 8.8 / 16.2 ms
# at CAP 32 / 64 / 128 / 256 / 512, and 31.7 ms with the two sorts it
# had), and not under 64: it must cover the top_k of 20-50 that chat
# clients send.
CAP = 64

# which way a step's filter went (the scalar that rides the step's
# output pytree; LLMEngine._record_step counts the last two)
PATH_NONE, PATH_BOUNDED, PATH_FULL_SORT = 0, 1, 2


def _thresholds(desc, whole_sum, top_k, top_p, use_k, use_p):
    """The ONE definition of the top-k / nucleus filter, as a threshold
    per row: entries under it are masked, so ties at it are kept.

    ``desc`` [B, n] holds each row's n largest scaled logits in
    descending order — the whole sorted row (n == vocab), or its CAP
    candidates; ``whole_sum`` [B] is ``sum(exp(row - max))`` over the
    WHOLE row, the nucleus' normaliser where no top-k mask applies.
    Top-k keeps the entries at or above the k-th largest.  The nucleus
    keeps the smallest prefix of the top-k survivors whose probability
    reaches p (entries whose cumulative mass *before* them is < p; the
    argmax always), normalised over those survivors.

    Returns ``(thresh [B], decided [B])``.  ``decided`` says that the n
    entries prove the threshold to be the whole row's: every top-k
    survivor is among them (the last one lies strictly under the k-th),
    or, without top-k, the nucleus closes before the last.  It means
    nothing where n == vocab."""
    n = desc.shape[-1]
    neg_inf = jnp.float32(-jnp.inf)
    k = jnp.maximum(top_k, 1)
    kth = jnp.take_along_axis(
        desc, (jnp.minimum(k, n) - 1)[:, None], axis=-1)[:, 0]
    thresh_k = jnp.where(use_k, kth, neg_inf)
    surv = desc >= thresh_k[:, None]
    e = jnp.where(surv, jnp.exp(desc - desc[:, :1]), 0.0)
    probs = e / jnp.where(use_k, jnp.sum(e, axis=-1), whole_sum)[:, None]
    cum = jnp.cumsum(probs, axis=-1)
    keep = surv & ((cum - probs) < top_p[:, None])
    thresh_p = jnp.where(
        use_p, jnp.min(jnp.where(keep, desc, jnp.inf), axis=-1), neg_inf)
    # (k > n clips the k-th to the last entry, which is not under itself)
    decided = jnp.where(
        use_k, desc[:, -1] < thresh_k, ~(use_p & keep[:, -1]))
    return jnp.maximum(thresh_k, thresh_p), decided


def _filtered_scaled(logits, temperature, top_k, top_p):
    """Temperature-scaled logits with the top-k/top-p filter applied —
    the ONE definition of the sampling distribution, shared by the draw
    path (``sample_tokens``, ``spec_verify_tokens``) and the spec-decode
    scoring path (``filtered_probs``), which must score exactly the
    distribution the draw path samples from.  Returns ``(scaled [B,
    vocab], path)`` with ``path`` one of ``PATH_*``.

    The thresholds come from the CAP largest entries of a row (one
    bounded selection) whenever those candidates decide every SAMPLING
    row's filter; a batch holding a row they cannot decide (``top_k``
    past CAP, ties running through the CAP-th candidate, ``top_p``
    alone over a distribution so flat that CAP entries do not reach p)
    sorts the whole vocabulary, once.  Either way the mask is ``scaled
    < thresh`` over the full row, so both paths keep the same set, ties
    included, up to float rounding of a cumulative mass against p.
    Greedy rows' filters are discarded downstream and neither trip the
    predicates nor get a mask (OpenAI clients routinely send top_p
    alongside temperature=0); a batch in which no sampling row asks for
    a filter skips even the selection."""
    safe_t = jnp.where(temperature <= 0.0, 1.0, temperature)
    scaled = logits / safe_t[:, None]
    sampling = temperature > 0.0
    use_k = sampling & (top_k > 0)
    use_p = sampling & (top_p < 1.0)
    vocab = scaled.shape[-1]
    n = min(CAP, vocab)

    def _filter(x):
        # the barrier keeps the selection a selection: where a
        # conditional's predicate depends on its output, the TPU
        # compiler (libtpu 0.0.34) otherwise expands the TopK custom
        # call into a sort of the whole row (15 ms for 2.6 at 64 rows;
        # PERF.md, PR 29; tests/ops/test_ragged_tpu_compile.py)
        cand = jax.lax.optimization_barrier(jax.lax.top_k(x, n)[0])
        whole_sum = jnp.sum(jnp.exp(x - cand[:, :1]), axis=-1)
        rows = (whole_sum, top_k, top_p, use_k, use_p)
        thresh, decided = _thresholds(cand, *rows)
        path = jnp.int32(PATH_BOUNDED)
        if n < vocab:  # else the candidates are the whole row
            bounded = jnp.all(decided)
            thresh = jax.lax.cond(
                bounded, lambda: thresh, lambda: _thresholds(
                    jnp.sort(x, axis=-1)[:, ::-1], *rows)[0])
            path = jnp.where(bounded, path, PATH_FULL_SORT)
        return jnp.where(x < thresh[:, None], _NEG_INF, x), path

    return jax.lax.cond(
        jnp.any(use_k | use_p), _filter,
        lambda x: (x, jnp.int32(PATH_NONE)), scaled)


@jax.jit
@jax.named_scope("omni.sampler")
def sample_tokens(
    logits: jax.Array,       # [B, vocab]
    temperature: jax.Array,  # [B]
    top_k: jax.Array,        # [B]
    top_p: jax.Array,        # [B]
    keys: jax.Array,         # [B, 2] key data
) -> tuple[jax.Array, jax.Array]:
    """Returns ``(token ids [B] i32, path)``: the sampled tokens and the
    scalar ``PATH_*`` the filter took.

    An all-greedy batch (the common serving case, and every step inside
    the greedy multi-step decode scan) skips the whole sampling branch
    with a lax.cond rather than computing it and discarding it through
    the final where."""
    logits = logits.astype(jnp.float32)
    greedy_ids = jnp.argmax(logits, axis=-1).astype(jnp.int32)

    def _sampled(_):
        scaled, path = _filtered_scaled(logits, temperature, top_k, top_p)

        def draw(key_data, row):
            return jax.random.categorical(
                jax.random.wrap_key_data(key_data), row)

        return jax.vmap(draw)(keys, scaled).astype(jnp.int32), path

    sampled_ids, path = jax.lax.cond(
        jnp.any(temperature > 0.0), _sampled,
        lambda _: (greedy_ids, jnp.int32(PATH_NONE)), None)
    return jnp.where(temperature <= 0.0, greedy_ids, sampled_ids), path


@jax.jit
@jax.named_scope("omni.sampler")
def spec_verify_tokens(
    cand_logits: jax.Array,  # [S, V, vocab] logits at candidate rows
    drafts: jax.Array,       # [S, V-1] draft token ids (0-padded)
    n_cand: jax.Array,       # [S] candidates per row (1 = plain sample)
    temperature: jax.Array,  # [S]
    top_k: jax.Array,        # [S]
    top_p: jax.Array,        # [S]
    keys: jax.Array,         # [S, 2] key data
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """ON-DEVICE speculative verify + accept for a batch of candidate
    rows — the accept-mask rebuild of the split path's host-side
    ``_run_spec_decode`` loop (which paid an argmax ``device_get``, a
    filtered-probs ``device_get``, and a numpy RNG walk per verify
    step).  Returns ``(tokens [S, V] i32, counts [S] i32, path)``: row
    ``s`` emits ``tokens[s, :counts[s]]``; ``path`` is the scalar
    ``PATH_*`` the filter took.

    Row semantics (``V`` = 1 + max draft length; row ``s`` carries
    ``n_cand[s] - 1`` real drafts):

    - plain rows (``n_cand == 1``): ``counts == 1`` and ``tokens[:, 0]``
      is EXACTLY ``sample_tokens(cand_logits[:, 0], ...)`` — greedy
      argmax or the same categorical draw from the same key, so folding
      plain sampling and verify into one executable changes no stream.
    - greedy verify (``temperature == 0``): accept the longest draft
      prefix matching per-position argmax, then the bonus argmax — the
      split path's accept loop, bit-identical.
    - sampled verify: rejection sampling against the filtered target
      distribution (accept draft ``d_j`` w.p. ``p_j(d_j)``; on
      rejection draw the replacement from ``p_j`` with ``d_j`` excluded
      and renormalized; full acceptance draws the bonus from the last
      candidate's distribution) — the emitted stream is exactly
      p-distributed.  Randomness is a deterministic per-(request, step,
      position) stream derived from ``keys``.
    """
    s, v, vocab = cand_logits.shape
    logits = cand_logits.astype(jnp.float32)
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)  # [S, V]
    if v > 1:
        j_idx = jnp.arange(v - 1)
        has_draft = j_idx[None, :] < (n_cand - 1)[:, None]  # [S, V-1]
        g_match = (drafts == greedy[:, :-1]) & has_draft
        g_counts = 1 + jnp.sum(
            jnp.cumprod(g_match.astype(jnp.int32), axis=1), axis=1)
    else:
        g_counts = jnp.ones((s,), jnp.int32)

    def _sampled(_):
        flat = logits.reshape(s * v, vocab)
        rep = lambda x: jnp.repeat(x, v)  # noqa: E731
        scaled, path = _filtered_scaled(flat, rep(temperature), rep(top_k),
                                        rep(top_p))
        scaled = scaled.reshape(s, v, vocab)
        base = jax.vmap(jax.random.wrap_key_data)(keys)
        # position-0 draw on the SAME stream as sample_tokens: a plain
        # row folded into the verify executable samples identically
        draw0 = jax.vmap(jax.random.categorical)(
            base, scaled[:, 0]).astype(jnp.int32)
        if v == 1:
            return draw0[:, None], jnp.ones((s,), jnp.int32), path
        probs = jax.nn.softmax(scaled, axis=-1)  # [S, V, vocab]
        # acceptance tests: u_j < p_j(d_j), stopped at the first miss
        u = jax.vmap(lambda k: jax.vmap(
            lambda j: jax.random.uniform(jax.random.fold_in(k, 1 + j))
        )(j_idx))(base)
        p_draft = jnp.take_along_axis(
            probs[:, :-1], drafts[..., None].astype(jnp.int32),
            axis=-1)[..., 0]
        accept = (u < p_draft) & has_draft
        r = jnp.sum(jnp.cumprod(accept.astype(jnp.int32), axis=1),
                    axis=1)                       # accepted drafts
        counts = (r + 1).astype(jnp.int32)
        # replacement draw per draft position: p_j \ {d_j} renormalized
        # (log-space categorical == draw from the renormalized dist);
        # a degenerate p_j(d_j) == 1 falls back to the argmax like the
        # split path's host loop did
        excl = jnp.where(jax.nn.one_hot(drafts, vocab, dtype=bool),
                         0.0, probs[:, :-1])
        excl_logits = jnp.where(excl > 0, jnp.log(excl), _NEG_INF)
        rkeys = jax.vmap(lambda k: jax.vmap(
            lambda j: jax.random.fold_in(k, 1001 + j))(j_idx))(base)
        repl = jax.vmap(jax.vmap(jax.random.categorical))(
            rkeys, excl_logits).astype(jnp.int32)
        repl = jnp.where(excl.sum(-1) > 0, repl,
                         jnp.argmax(probs[:, :-1], axis=-1)
                         .astype(jnp.int32))
        # bonus draw from the row's LAST candidate distribution
        last = jnp.maximum(n_cand - 1, 0).astype(jnp.int32)
        bonus_logits = jnp.take_along_axis(
            jnp.where(probs > 0, jnp.log(probs), _NEG_INF),
            last[:, None, None], axis=1)[:, 0]
        bonus = jax.vmap(jax.random.categorical)(
            jax.vmap(lambda k: jax.random.fold_in(k, 2001))(base),
            bonus_logits).astype(jnp.int32)
        # assemble: accepted drafts below r, replacement-or-bonus at r
        pad = jnp.zeros((s, 1), jnp.int32)
        drafts_pad = jnp.concatenate(
            [drafts.astype(jnp.int32), pad], axis=1)     # [S, V]
        repl_pad = jnp.concatenate([repl, pad], axis=1)
        at_r = jnp.where(
            r == (n_cand - 1),
            bonus, jnp.take_along_axis(repl_pad, r[:, None], axis=1)[:, 0])
        pos = jnp.arange(v)[None, :]
        toks = jnp.where(pos == r[:, None], at_r[:, None], drafts_pad)
        # plain rows keep the sample_tokens-identical position-0 draw
        plain = (n_cand <= 1)
        toks = toks.at[:, 0].set(jnp.where(plain, draw0, toks[:, 0]))
        counts = jnp.where(plain, 1, counts)
        return toks, counts, path

    s_toks, s_counts, path = jax.lax.cond(
        jnp.any(temperature > 0.0), _sampled,
        lambda _: (greedy, g_counts, jnp.int32(PATH_NONE)), None)
    is_greedy = temperature <= 0.0
    tokens = jnp.where(is_greedy[:, None], greedy, s_toks)
    counts = jnp.where(is_greedy, g_counts, s_counts)
    return tokens.astype(jnp.int32), counts.astype(jnp.int32), path


@functools.partial(jax.jit, static_argnums=(2,))
@jax.named_scope("omni.logprobs")
def compute_logprobs(
    logits: jax.Array,   # [B, vocab]
    tokens: jax.Array,   # [B] sampled ids
    k: int,
):
    """Log-softmax logprob of each sampled token plus the top-k
    alternatives (OpenAI logprobs semantics; reference rides vLLM's
    sampler logprobs)."""
    lp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    chosen = jnp.take_along_axis(lp, tokens[:, None].astype(jnp.int32),
                                 axis=-1)[:, 0]
    if k <= 0:
        b = logits.shape[0]
        return chosen, jnp.zeros((b, 0), jnp.float32),             jnp.zeros((b, 0), jnp.int32)
    top_v, top_i = jax.lax.top_k(lp, k)
    return chosen, top_v, top_i.astype(jnp.int32)


@jax.jit
@jax.named_scope("omni.sampler")
def filtered_probs(
    logits: jax.Array,       # [B, vocab]
    temperature: jax.Array,  # [B]
    top_k: jax.Array,        # [B]
    top_p: jax.Array,        # [B]
) -> jax.Array:
    """Per-row TARGET distribution under the request's sampling params
    (temperature + top-k/top-p filtering); temperature 0 rows become a
    one-hot at the argmax.  The spec-decode verify step scores draft
    tokens against exactly the distribution ``sample_tokens`` would draw
    from (reference: rejection sampling in the verify path,
    gpu_ar_model_runner.py:466-497)."""
    logits = logits.astype(jnp.float32)
    vocab = logits.shape[-1]
    greedy = jax.nn.one_hot(jnp.argmax(logits, axis=-1), vocab)
    probs = jax.nn.softmax(
        _filtered_scaled(logits, temperature, top_k, top_p)[0], axis=-1)
    return jnp.where((temperature <= 0.0)[:, None], greedy, probs)
