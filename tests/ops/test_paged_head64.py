"""Heads of 64, two to a 128-lane row of the pool (``ops/paged_attention.py
heads_a_row``): both entries of the paged-attention kernel (interpret mode
on the CPU) against the plain references over an UNPAIRED pool of 64-wide
rows, at a group of 4 as LFM2-8B-A1B has it, contexts that end inside a
page, and rows whose two heads hold different numbers (every row here:
the draws are independent a head).

Tolerance: the kernel and the reference are float32 and differ by the
order of the online softmax's sums and by the other head's lanes, which
enter every score as products with zero: 1e-5 holds both, a head read
from the wrong half of its row is off by the numbers' own size."""

import importlib

import jax.numpy as jnp
import numpy as np
import pytest

from vllm_omni_tpu.ops import (
    paged_attention,
    ragged_paged_attention,
    ragged_paged_attention_ref,
    write_kv_cache,
)
from vllm_omni_tpu.ops.ragged_paged_attention import align_to_block

# the module, not the op of the same name that the package exports
pa = importlib.import_module("vllm_omni_tpu.ops.paged_attention")

PAGE, D, TB = 16, 64, 8


def _pools(specs, hkv, seed):
    """Per-sequence (ctx, q) specs -> the paired pools the engine keeps
    ([hkv / 2, P, 16, 128], written through ``write_kv_cache`` from
    [ctx, hkv, 64] rows), the same rows in an unpaired pool ([hkv, P, 16,
    64]) for the references, the tables and the dense rows."""
    rng = np.random.default_rng(seed)
    num_pages = 2 + sum(-(-c // PAGE) for c, _ in specs)
    (kp, vp), = pa.init_kv_cache(1, num_pages, PAGE, hkv // 2, 2 * D,
                                 jnp.float32)
    (ku, vu), = pa.init_kv_cache(1, num_pages, PAGE, hkv, D, jnp.float32)
    tables = np.zeros((len(specs), 8), np.int32)
    nxt, dense = 1, []
    for i, (ctx, _) in enumerate(specs):
        ids = list(range(nxt, nxt + -(-ctx // PAGE)))
        nxt += len(ids)
        tables[i, :len(ids)] = ids
        k = rng.standard_normal((ctx, hkv, D)).astype(np.float32)
        v = rng.standard_normal((ctx, hkv, D)).astype(np.float32)
        dense.append((k, v))
        slots = jnp.asarray([ids[p // PAGE] * PAGE + p % PAGE
                             for p in range(ctx)], jnp.int32)
        kp, vp = write_kv_cache(kp, vp, jnp.asarray(k), jnp.asarray(v), slots)
        ku, vu = write_kv_cache(ku, vu, jnp.asarray(k), jnp.asarray(v), slots)
    return (kp, vp), (ku, vu), jnp.asarray(tables), dense


MIXED = {
    # a chunk over prior context, decode rows, a fresh chunk; every
    # context ends inside a page (16)
    "mixed": [(37, 9), (21, 1), (13, 13), (50, 1)],
    "decode_rows": [(9, 1), (33, 1), (17, 1)],
    "chunk_resume": [(45, 19), (20, 5)],
}


@pytest.mark.parametrize("hkv", [2, 4])
@pytest.mark.parametrize("name", sorted(MIXED))
def test_the_wide_path_serves_paired_rows(name, hkv):
    specs = MIXED[name]
    h = 4 * hkv  # a group of 4
    paired, plain, tables, _ = _pools(specs, hkv, seed=hkv)
    rng = np.random.default_rng(99)
    s = len(specs)
    cu, total = np.zeros(s + 1, np.int32), 0
    for i, (_, qn) in enumerate(specs):
        cu[i] = total
        total += align_to_block(qn, TB)
    cu[s] = total
    q = np.zeros((total, h, D), np.float32)
    for i, (_, qn) in enumerate(specs):
        q[cu[i]:cu[i] + qn] = rng.standard_normal((qn, h, D))
    args = (tables, jnp.asarray(cu),
            jnp.asarray([qn for _, qn in specs], jnp.int32),
            jnp.asarray([c for c, _ in specs], jnp.int32), s)
    got = ragged_paged_attention(jnp.asarray(q), *paired, *args,
                                 use_pallas=True)
    want = ragged_paged_attention_ref(jnp.asarray(q), *plain, *args)
    assert got.shape == (total, h, D)
    for i, (_, qn) in enumerate(specs):
        rows = slice(cu[i], cu[i] + qn)
        np.testing.assert_allclose(got[rows], want[rows], atol=1e-5)
    # and the reference path of the same entry reads the paired rows too
    ref_entry = ragged_paged_attention(jnp.asarray(q), *paired, *args,
                                       use_pallas=False)
    for i, (_, qn) in enumerate(specs):
        rows = slice(cu[i], cu[i] + qn)
        np.testing.assert_allclose(ref_entry[rows], want[rows], atol=1e-5)


@pytest.mark.parametrize("hkv", [2, 8])
def test_the_decode_entry_serves_paired_rows(hkv):
    """One token a sequence; 8 kv heads and 32 query heads are the
    published model's."""
    specs = [(9, 1), (33, 1), (17, 1), (64, 1), (1, 1)]
    h = 4 * hkv
    paired, plain, tables, _ = _pools(specs, hkv, seed=3)
    q = jnp.asarray(np.random.default_rng(5).standard_normal(
        (len(specs), h, D)).astype(np.float32))
    lens = jnp.asarray([c for c, _ in specs], jnp.int32)
    got = paged_attention(q, *paired, tables, lens, use_pallas=True)
    want = pa.paged_attention_ref(q, *plain, tables, lens)
    assert got.shape == (len(specs), h, D)
    np.testing.assert_allclose(got, want, atol=1e-5)
    np.testing.assert_allclose(
        paged_attention(q, *paired, tables, lens, use_pallas=False), want,
        atol=1e-5)


def test_a_query_head_reads_its_own_half_of_the_row():
    """Head h attends kv head h // 4: with the second kv head's keys and
    values zeroed in the pool, the first kv head's query heads read what
    they read before and the second's read the mean of zeros."""
    specs = [(23, 1)]
    (kp, vp), plain, tables, _ = _pools(specs, 2, seed=11)
    q = jnp.asarray(np.random.default_rng(6).standard_normal(
        (1, 8, D)).astype(np.float32))
    lens = jnp.asarray([23], jnp.int32)
    full = paged_attention(q, kp, vp, tables, lens, use_pallas=True)
    half = paged_attention(q, kp.at[..., D:].set(0), vp.at[..., D:].set(0),
                           tables, lens, use_pallas=True)
    np.testing.assert_allclose(half[:, :4], full[:, :4], atol=1e-6)
    assert float(jnp.abs(half[:, 4:]).max()) == 0.0
    assert float(jnp.abs(full[:, 4:]).max()) > 0.1


def test_the_write_lays_two_heads_in_a_row_and_pads_nothing():
    specs = [(21, 1)]
    (kp, vp), _, tables, dense = _pools(specs, 4, seed=12)
    k, v = dense[0]
    assert kp.shape == (2, 4, PAGE, 128)
    page, off = int(tables[0, 1]), 4                     # token 20
    for row in range(2):
        got_k, got_v = kp[row, page, off], vp[row, page, off]
        np.testing.assert_array_equal(got_k[:D], k[20, 2 * row])
        np.testing.assert_array_equal(got_k[D:], k[20, 2 * row + 1])
        np.testing.assert_array_equal(got_v[D:], v[20, 2 * row + 1])
    # 2 pools x 2 rows x 128 lanes = K and V of 4 heads of 64: no lane more
    assert 2 * kp.shape[0] * kp.shape[3] == 2 * 4 * D


def test_tiling_reasons_for_64_128_and_96():
    assert pa.paged_tiling_reason(64, 16) == ""       # two heads to a row
    assert pa.paged_tiling_reason(128, 16) == ""
    assert pa.paged_tiling_reason(256, 16) == ""
    assert "head_dim 96" in pa.paged_tiling_reason(96, 16)
    # an odd count of 64-wide kv heads cannot pair; a pool of 64-wide rows
    # is what the kernels cannot tile
    assert "head_dim 64" in pa.paged_tiling_reason(64, 16, num_kv_heads=1)
    assert "head_dim 64" in pa.row_tiling_reason(64, 16)
    assert pa.heads_a_row(64, 8) == 2 and pa.heads_a_row(64, 3) == 1
    assert pa.heads_a_row(128, 4) == pa.heads_a_row(32, 4) == 1
    assert "page_size 4" in pa.paged_tiling_reason(64, 4)


@pytest.mark.parametrize("hkv, h, d", [(4, 32, 128), (2, 16, 256),
                                        (1, 20, 640)],
                         ids=["thinker", "qwen3next", "glm"])
def test_rows_as_wide_as_a_head_are_left_as_they_were(hkv, h, d):
    """The thinker's, Qwen3-Next's and GLM's pools hold one head a row:
    their queries, their rows and their outputs pass through untouched (the
    same arrays, so the same programs; tests/ops/test_ragged_tpu_compile.py
    holds the programs' hashes)."""
    pool = jnp.zeros((hkv, 2, PAGE, d), jnp.bfloat16)
    q = jnp.ones((3, h, d), jnp.bfloat16)
    new = jnp.ones((3, hkv, d), jnp.bfloat16)
    same, scale = pa.pair_queries(q, pool)
    assert same is q and scale is None
    assert pa.own_half(q, pool, d) is q
    assert pa.paired_rows(new, pool) is new
