import re

import jax
import jax.numpy as jnp
import ml_dtypes
import numpy as np
import pytest

from vllm_omni_tpu.sample import sampler
from vllm_omni_tpu.sample.sampler import SamplingTensors, sample_tokens
from vllm_omni_tpu.sampling_params import SamplingParams
from vllm_omni_tpu.worker.model_runner import _PAD_SAMPLING


def _sample(logits, params, step=1):
    t = SamplingTensors.build(params, step=step)
    return np.asarray(sample_tokens(
        jnp.asarray(logits), t.temperature, t.top_k, t.top_p, t.keys)[0])


def test_greedy_matches_argmax():
    logits = np.random.RandomState(0).randn(4, 32).astype(np.float32)
    toks = _sample(logits, [SamplingParams(temperature=0.0)] * 4)
    np.testing.assert_array_equal(toks, logits.argmax(-1))


def test_top_k_one_is_greedy():
    logits = np.random.RandomState(1).randn(3, 64).astype(np.float32)
    toks = _sample(logits, [SamplingParams(temperature=1.0, top_k=1, seed=7)] * 3)
    np.testing.assert_array_equal(toks, logits.argmax(-1))


def test_top_p_tiny_is_greedy():
    logits = np.random.RandomState(2).randn(3, 64).astype(np.float32)
    toks = _sample(logits, [SamplingParams(temperature=1.0, top_p=1e-6, seed=3)] * 3)
    np.testing.assert_array_equal(toks, logits.argmax(-1))


def test_mixed_batch_greedy_and_random():
    logits = np.random.RandomState(3).randn(2, 16).astype(np.float32)
    params = [SamplingParams(temperature=0.0),
              SamplingParams(temperature=2.0, seed=11)]
    toks = _sample(logits, params)
    assert toks[0] == logits[0].argmax()
    assert 0 <= toks[1] < 16


def test_seeded_determinism_and_step_variation():
    logits = np.random.RandomState(4).randn(1, 1000).astype(np.float32)
    p = [SamplingParams(temperature=1.0, seed=5)]
    a = _sample(logits, p, step=1)
    b = _sample(logits, p, step=1)
    np.testing.assert_array_equal(a, b)
    # different steps should (overwhelmingly) differ over many draws
    draws = {int(_sample(logits, p, step=s)[0]) for s in range(20)}
    assert len(draws) > 1


def test_top_k_restricts_support():
    rs = np.random.RandomState(6)
    logits = rs.randn(1, 100).astype(np.float32)
    top5 = set(np.argsort(logits[0])[-5:])
    for s in range(50):
        tok = _sample(logits, [SamplingParams(temperature=5.0, top_k=5, seed=s)],
                      step=s)[0]
        assert int(tok) in top5


# ---------------------------------------------------------------- PR 29
# The filter takes its thresholds from the CAP largest entries of a row
# where those decide it, and from one sort of the whole row otherwise.
# The reference below is the definition as it stood before: two masks,
# each from a sort of its own, in numpy.

CAP = sampler.CAP
VOCAB = 32768
_CELLS = dict(temperature=0.7, top_k=20, top_p=0.8)


def _two_sort_kept(scaled, k, p):
    """One row's kept set by the two-sort definition (numpy, the masses
    summed in float64: the definition without a rounding of its own)."""
    x = scaled.astype(np.float32).copy()
    if k > 0:
        x[x < np.sort(x)[::-1][min(k, x.size) - 1]] = -1e30
    if p < 1.0:
        desc = np.sort(x)[::-1]
        e = np.exp(desc.astype(np.float64) - desc[0])
        probs = e / e.sum()
        keep = (np.cumsum(probs) - probs) < p
        x[x < desc[keep].min()] = -1e30
    return x > -1e29


def _assert_kept(kept, scaled, k, p, name=None):
    """``kept`` is the two-sort definition's set, but for entries whose
    cumulative mass lies within float rounding of p (the sums are taken
    over other lengths and in another order)."""
    lo = _two_sort_kept(scaled, k, p - 1e-5) if p < 1.0 else None
    hi = _two_sort_kept(scaled, k, min(p + 1e-5, 1.0))
    if lo is not None and not np.array_equal(lo, hi):
        assert (lo <= kept).all() and (kept <= hi).all(), name
        assert hi.sum() - lo.sum() < 1e-3 * kept.size, name
    else:
        np.testing.assert_array_equal(kept, hi, err_msg=str(name))


def _bf16_row(seed, vocab=VOCAB, scale=3.0):
    """Random logits rounded to bfloat16: ties near the top are common."""
    row = scale * np.random.RandomState(seed).randn(vocab)
    return row.astype(ml_dtypes.bfloat16).astype(np.float32)


def _tied_row(seed, first, last, vocab=VOCAB):
    """Distinct values but for one run of equal ones at ranks
    first..last (0-based, descending)."""
    desc = np.sort(_bf16_row(seed, vocab) + np.linspace(0, 1e-3, vocab)
                   .astype(np.float32))[::-1].copy()
    desc[first:last + 1] = desc[first]
    return desc[np.random.RandomState(seed + 1).permutation(vocab)]


def _flat_row(seed, vocab=VOCAB):
    return (1e-3 * np.random.RandomState(seed).randn(vocab)
            ).astype(np.float32)


# name -> (logits row, temperature, top_k, top_p, the candidates decide it)
_ROWS = {
    "k1": (_bf16_row(1), 0.7, 1, 1.0, True),
    "k20_p08_cells": (_bf16_row(2), 0.7, 20, 0.8, True),
    "k20_p1": (_bf16_row(3), 0.7, 20, 1.0, True),
    "k20_tie_at_threshold": (_tied_row(4, 17, 24), 0.7, 20, 1.0, True),
    "k20_tie_inside_nucleus": (_tied_row(4, 17, 24), 8.0, 20, 0.9, True),
    "k20_ties_through_cap": (_tied_row(5, 15, CAP + 5), 0.7, 20, 0.8, False),
    "k_cap_less_1": (_tied_row(6, 3, 4), 1.0, CAP - 1, 0.95, True),
    "k_cap": (_bf16_row(7), 1.0, CAP, 0.95, False),
    "k_cap_plus_1": (_bf16_row(8), 1.0, CAP + 1, 0.95, False),
    "k0_peaked_p08": (_bf16_row(9, scale=6.0), 0.7, 0, 0.8, True),
    "k0_flat_p099": (_flat_row(10), 1.0, 0, 0.99, False),
    "k0_p1_no_filter": (_flat_row(11), 1.0, 0, 1.0, True),
    "greedy_with_filters": (_flat_row(12), 0.0, CAP + 1, 0.5, True),
    "padding": (_flat_row(13), _PAD_SAMPLING.temperature,
                _PAD_SAMPLING.top_k, _PAD_SAMPLING.top_p, True),
}


def _columns(names, vocab=VOCAB):
    rows = [_ROWS[n] for n in names]
    return (jnp.asarray(np.stack([r[0][:vocab] for r in rows])),
            jnp.asarray([r[1] for r in rows], jnp.float32),
            jnp.asarray([r[2] for r in rows], jnp.int32),
            jnp.asarray([r[3] for r in rows], jnp.float32))


@pytest.fixture(scope="module")
def both_internals():
    """Every row of _ROWS through both internals, called directly: the
    thresholds from the CAP candidates (with ``decided``) and from the
    whole sorted row."""
    names = list(_ROWS)
    logits, temp, top_k, top_p = _columns(names)

    @jax.jit
    def run(logits, temp, top_k, top_p):
        scaled = logits / jnp.where(temp <= 0, 1.0, temp)[:, None]
        cand = jax.lax.top_k(scaled, CAP)[0]
        rows = (jnp.sum(jnp.exp(scaled - cand[:, :1]), axis=-1), top_k,
                top_p, (temp > 0) & (top_k > 0), (temp > 0) & (top_p < 1))
        bounded, decided = sampler._thresholds(cand, *rows)
        full, _ = sampler._thresholds(
            jnp.sort(scaled, axis=-1)[:, ::-1], *rows)
        return scaled, bounded, decided, full

    out = [np.asarray(a) for a in run(logits, temp, top_k, top_p)]
    return {n: tuple(a[i] for a in out) for i, n in enumerate(names)}


@pytest.mark.parametrize("name", list(_ROWS))
def test_bounded_kept_set_is_the_full_sorts(name, both_internals):
    scaled, bounded, decided, full = both_internals[name]
    _, temp, top_k, top_p, want_decided = _ROWS[name]
    assert bool(decided) == want_decided
    if temp <= 0:  # greedy and padding rows: no mask, whatever they ask
        assert bounded == full == -np.inf
        return
    want = _two_sort_kept(scaled, top_k, top_p)
    _assert_kept(scaled >= full, scaled, top_k, top_p)
    if decided:
        _assert_kept(scaled >= bounded, scaled, top_k, top_p)
    if name in ("k20_tie_at_threshold", "k20_tie_inside_nucleus"):
        # ranks 17..24 are equal: the top-20 mask keeps 25, and a nucleus
        # that ends inside the run keeps the whole run
        assert want.sum() == 25
    if name == "k0_p1_no_filter":
        assert want.all() and bounded == -np.inf


_INSIDE = [n for n, r in _ROWS.items() if r[4]]
_OUTSIDE = [n for n, r in _ROWS.items() if not r[4]]


@pytest.mark.parametrize("extra", [None] + _OUTSIDE)
def test_batch_predicate_sends_exactly_the_undecided_to_the_sort(extra):
    """A batch of rows the candidates decide (greedy and padding rows
    with any filter among them) takes the bounded path; any one row
    they cannot decide sends the batch to the one sort; the kept sets
    are the two-sort definition's either way."""
    names = _INSIDE + ([extra] if extra else [])
    logits, temp, top_k, top_p = _columns(names)
    scaled, path = jax.jit(sampler._filtered_scaled)(
        logits, temp, top_k, top_p)
    assert int(path) == (sampler.PATH_FULL_SORT if extra
                         else sampler.PATH_BOUNDED)
    scaled = np.asarray(scaled)
    for i, n in enumerate(names):
        row, t, k, p, _ = _ROWS[n]
        if t > 0:
            _assert_kept(scaled[i] > -1e29, row / np.float32(t), k, p, n)
        else:
            np.testing.assert_array_equal(scaled[i], row, err_msg=n)


@pytest.mark.parametrize("names,want", [
    (["greedy_with_filters", "padding"], sampler.PATH_NONE),
    (["k0_p1_no_filter", "greedy_with_filters"], sampler.PATH_NONE),
    (["k0_p1_no_filter", "k20_p1"], sampler.PATH_BOUNDED),
])
def test_no_filter_asked_skips_the_selection(names, want):
    logits, temp, top_k, top_p = _columns(names)
    keys = SamplingTensors.build(
        [SamplingParams(seed=3)] * len(names), step=1).keys
    toks, path = sample_tokens(logits, temp, top_k, top_p, keys)
    assert int(path) == want
    np.testing.assert_array_equal(
        np.asarray(toks)[np.asarray(temp) <= 0],
        np.asarray(logits).argmax(-1)[np.asarray(temp) <= 0])


def test_vocabulary_smaller_than_cap():
    """min(CAP, vocab): the candidates are the whole row, so every
    filter is decided, top_k past CAP and a flat nucleus included."""
    vocab = CAP - 14
    names = list(_ROWS)
    logits, temp, top_k, top_p = _columns(names, vocab)
    scaled, path = jax.jit(sampler._filtered_scaled)(
        logits, temp, top_k, top_p)
    assert int(path) == sampler.PATH_BOUNDED
    for i, n in enumerate(names):
        row, t, k, p, _ = _ROWS[n]
        if t > 0:
            _assert_kept(np.asarray(scaled[i]) > -1e29,
                         row[:vocab] / np.float32(t), k, p, n)


def _cells_batch(vocab, last_top_k):
    """Eight rows at the cells' settings and a ninth whose top_k picks
    the path for the whole batch."""
    logits = np.stack([_bf16_row(100 + i, vocab) for i in range(9)])
    params = [SamplingParams(seed=40 + i, **_CELLS) for i in range(8)]
    params.append(SamplingParams(seed=48, temperature=0.7,
                                 top_k=last_top_k, top_p=0.8))
    t = SamplingTensors.build(params, step=5)
    return jnp.asarray(logits), t


def test_seeded_draws_are_token_identical_on_both_paths():
    """At the cells' settings the kept set is the same on both paths,
    so the seeded stream is: the same batch but for the ninth row's
    top_k (20: bounded; CAP + 1: the sort) draws the same eight tokens,
    and they are the two-sort definition's."""
    vocab = 152064
    out = {}
    for last in (20, CAP + 1):
        logits, t = _cells_batch(vocab, last)
        toks, path = sample_tokens(logits, t.temperature, t.top_k, t.top_p,
                                   t.keys)
        out[int(path)] = np.asarray(toks)[:8]
    assert set(out) == {sampler.PATH_BOUNDED, sampler.PATH_FULL_SORT}
    np.testing.assert_array_equal(out[sampler.PATH_BOUNDED],
                                  out[sampler.PATH_FULL_SORT])
    logits, t = _cells_batch(vocab, 20)
    scaled = np.asarray(logits)[:8] / np.float32(0.7)
    masked = np.where(
        np.stack([_two_sort_kept(r, 20, 0.8) for r in scaled]),
        scaled, np.float32(-1e30))
    want = jax.vmap(lambda kd, row: jax.random.categorical(
        jax.random.wrap_key_data(kd), row))(t.keys[:8], jnp.asarray(masked))
    np.testing.assert_array_equal(out[sampler.PATH_BOUNDED],
                                  np.asarray(want))


def test_lowered_sampler_sorts_the_vocabulary_at_most_once():
    """The text lowered for [64, 152064] (read, not executed) holds one
    full-vocabulary sort, inside the conditional that the batch
    predicate guards, beside one bounded selection."""
    b, v = 64, 152064
    f32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32)  # noqa: E731
    text = sample_tokens.lower(
        f32(b, v), f32(b), jax.ShapeDtypeStruct((b,), jnp.int32), f32(b),
        jax.ShapeDtypeStruct((b, 2), jnp.uint32)).as_text()
    lines = text.splitlines()
    assert text.count("stablehlo.sort") == 1
    assert len(re.findall(rf"chlo\.top_k\(.*k = {CAP}\)", text)) == 1
    calls = [i for i, ln in enumerate(lines) if "call @sort(" in ln]
    assert len(calls) == 1
    indent = lambda ln: len(ln) - len(ln.lstrip())  # noqa: E731
    # the nearest enclosing region opener above the call is a case of
    # its own, nested inside the two outer ones (any sampling row; any
    # filter asked)
    enclosing = [ln for ln in lines[:calls[0]]
                 if indent(ln) < indent(lines[calls[0]])
                 and '"stablehlo.case"' in ln]
    assert len(enclosing) == 3


@pytest.mark.parametrize("last_top_k,want_path", [
    (20, sampler.PATH_BOUNDED), (CAP + 1, sampler.PATH_FULL_SORT)])
def test_spec_verify_and_filtered_probs_share_the_filter(last_top_k,
                                                          want_path):
    """At top_k 20 / top_p 0.8, on either path: ``filtered_probs`` is
    the softmax of the filter ``sample_tokens`` draws from, and a plain
    row of ``spec_verify_tokens`` is ``sample_tokens``' token."""
    from vllm_omni_tpu.sample.sampler import (
        filtered_probs,
        spec_verify_tokens,
    )

    logits, t = _cells_batch(4096, last_top_k)
    cols = (t.temperature, t.top_k, t.top_p)
    toks, path = sample_tokens(logits, *cols, t.keys)
    assert int(path) == want_path
    probs = np.asarray(filtered_probs(logits, *cols))
    scaled = np.asarray(logits) / np.float32(0.7)
    for i in range(8):
        kept = _two_sort_kept(scaled[i], 20, 0.8)
        np.testing.assert_array_equal(probs[i] > 0, kept)
        e = np.exp(scaled[i][kept] - scaled[i].max())
        np.testing.assert_allclose(probs[i][kept], e / e.sum(), rtol=1e-5)
    # V = 3 candidate rows a sequence: the first 8 plain, the ninth
    # verifying two drafts
    cand = jnp.stack([logits, logits[::-1], logits], axis=1)
    n_cand = jnp.asarray([1] * 8 + [3], jnp.int32)
    drafts = jnp.zeros((9, 2), jnp.int32)
    tk, ct, spath = spec_verify_tokens(cand, drafts, n_cand, *cols, t.keys)
    assert int(spath) == want_path
    np.testing.assert_array_equal(np.asarray(ct)[:8], 1)
    np.testing.assert_array_equal(np.asarray(tk)[:8, 0],
                                  np.asarray(toks)[:8])
