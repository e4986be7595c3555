"""The rotary embedding that pairs NEIGHBOURING dims (GPT-J's;
``position_embedding_type`` ``rope_gptj``, command-a-plus-05-2026's): the
oracle of ``ops/rope.py`` against the published formula written out dim by
dim, and the served form (the half-split rotation over q and k columns
permuted once in the weight tree, ``pairs_to_halves``) against it: equal
scores, through the plain rotation and through the Pallas kernel."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.archs import cohere2_moe as arch
from vllm_omni_tpu.ops import (
    apply_rope,
    apply_rope_pairs_ref,
    apply_rope_ref,
    compute_rope_freqs,
    pairs_to_halves,
)

T, HEADS, D, THETA = 24, 4, 128, 50000.0


def _published(x, positions, theta):
    """``(y[2i], y[2i+1]) = (x[2i] c_i - x[2i+1] s_i, x[2i+1] c_i + x[2i]
    s_i)``, ``theta_i = theta^(-2i/d)``, one number at a time."""
    x = np.asarray(x, np.float64)
    y = np.zeros_like(x)
    d = x.shape[-1]
    for t, pos in enumerate(positions):
        for i in range(d // 2):
            angle = pos * theta ** (-2.0 * i / d)
            c, s = np.cos(angle), np.sin(angle)
            y[t, :, 2 * i] = x[t, :, 2 * i] * c - x[t, :, 2 * i + 1] * s
            y[t, :, 2 * i + 1] = x[t, :, 2 * i + 1] * c + x[t, :, 2 * i] * s
    return y


@pytest.fixture(scope="module")
def case():
    kq, kk, kh = jax.random.split(jax.random.PRNGKey(0), 3)
    hidden = jax.random.normal(kh, (T, 64), jnp.float32)
    w_q = jax.random.normal(kq, (64, HEADS * D), jnp.float32) / 8
    w_k = jax.random.normal(kk, (64, HEADS * D), jnp.float32) / 8
    # positions around the window and to the end of the check's prompt
    # (a float32 angle at position p is off by some p x 6e-8 radians from
    # the float64 formula below: 5e-4 at 8 199, which the tolerances hold)
    positions = jnp.asarray([0, 1, 2, 3, 17, 4095, 4096, 4097] + list(
        range(8200 - (T - 8), 8200)), jnp.int32)
    return hidden, w_q, w_k, positions


def test_the_oracle_is_the_published_formula(case):
    hidden, w_q, _, positions = case
    x = (hidden @ w_q).reshape(T, HEADS, D)
    cos, sin = compute_rope_freqs(positions, D, THETA)
    want = _published(x, np.asarray(positions), THETA)
    np.testing.assert_allclose(apply_rope_pairs_ref(x, cos, sin), want,
                               atol=3e-3)
    # the benchmark's reference writes the same formula on its own
    np.testing.assert_allclose(arch._rope_pairs(x, positions, THETA), want,
                               atol=3e-3)
    # and it is not the half-split rotation
    assert np.abs(np.asarray(apply_rope_ref(x, cos, sin)) - want).max() > 0.1


@pytest.mark.parametrize("use_pallas", [False, True])
def test_permuted_columns_give_the_published_scores(case, use_pallas):
    """``q . k`` of every pair of positions and every head: neighbouring
    pairs over the checkpoint's columns, against the half-split rotation
    (plain, and the Pallas kernel in interpret mode) over
    ``pairs_to_halves`` of them."""
    hidden, w_q, w_k, positions = case
    cos, sin = compute_rope_freqs(positions, D, THETA)

    def scores(q, k):
        return jnp.einsum("thd,shd->hts", q, k)

    want = scores(
        apply_rope_pairs_ref((hidden @ w_q).reshape(T, HEADS, D), cos, sin),
        apply_rope_pairs_ref((hidden @ w_k).reshape(T, HEADS, D), cos, sin))
    served = [apply_rope((hidden @ pairs_to_halves(w, D)).reshape(
        T, HEADS, D), cos, sin, use_pallas=use_pallas) for w in (w_q, w_k)]
    np.testing.assert_allclose(scores(*served), want, rtol=1e-4, atol=2e-3)
    # unpermuted columns under the half-split rotation are other scores
    plain = [apply_rope_ref((hidden @ w).reshape(T, HEADS, D), cos, sin)
             for w in (w_q, w_k)]
    assert float(jnp.abs(scores(*plain) - want).max()) > 1.0


def test_the_permutation_and_the_references_inverse(case):
    """A head's columns evens first, then odds; the benchmark's reference
    puts them back; without a rotation the scores never knew."""
    _, w_q, w_k, _ = case
    served = pairs_to_halves(w_q, D)
    head = np.asarray(w_q[:, D:2 * D])
    np.testing.assert_array_equal(
        np.asarray(served[:, D:2 * D]),
        np.concatenate([head[:, 0::2], head[:, 1::2]], axis=1))
    np.testing.assert_array_equal(arch._published_order(served, D), w_q)
    x = jax.random.normal(jax.random.PRNGKey(9), (5, 64), jnp.float32)
    np.testing.assert_allclose(
        jnp.einsum("td,sd->ts", x @ served, x @ pairs_to_halves(w_k, D)),
        jnp.einsum("td,sd->ts", x @ w_q, x @ w_k), rtol=1e-5, atol=1e-4)
