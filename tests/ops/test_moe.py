"""Routed MoE (grouped matmul + EP shard_map) vs the dense-dispatch
oracle (VERDICT r1 weak#4: dense dispatch wastes k/E of the FLOPs; the
routed path must match it exactly).  Reference semantics: vLLM fused MoE
consumed by the Qwen3 thinker/talker (models/qwen3_omni/qwen3_moe.py)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from vllm_omni_tpu.models.common import transformer as tfm
from vllm_omni_tpu.ops import moe as moe_ops
from vllm_omni_tpu.parallel.mesh import MeshConfig, build_mesh


@pytest.fixture(autouse=True)
def _clear_ep_mesh():
    yield
    moe_ops.set_ep_mesh(None)


def _mk_weights(rng, t=12, hidden=16, e=4, inter=8, k=2):
    k1, k2, k3, k4 = jax.random.split(rng, 4)
    x = jax.random.normal(k1, (t, hidden), jnp.float32)
    router_w = jax.random.normal(k2, (hidden, e), jnp.float32) * 0.5
    gate_up = jax.random.normal(k3, (e, hidden, 2 * inter), jnp.float32) * 0.2
    down = jax.random.normal(k4, (e, inter, hidden), jnp.float32) * 0.2
    return x, router_w, gate_up, down


def _dense_oracle(x, router_w, gate_up, down, k):
    layer = {"router": {"w": router_w},
             "experts": {"gate_up": gate_up, "down": down}}
    cfg = tfm.TransformerConfig(
        moe=True, num_experts=gate_up.shape[0], num_experts_per_tok=k)
    return tfm._moe_mlp_dense(layer, cfg, x)


def test_routed_matches_dense(rng):
    x, rw, gu, dn = _mk_weights(rng)
    want = _dense_oracle(x, rw, gu, dn, 2)
    got = moe_ops.routed_moe(x, rw, gu, dn, 2)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-5, rtol=1e-4)


def test_routed_topk1(rng):
    x, rw, gu, dn = _mk_weights(rng, e=3)
    want = _dense_oracle(x, rw, gu, dn, 1)
    got = moe_ops.routed_moe(x, rw, gu, dn, 1)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-5, rtol=1e-4)


def test_routed_under_jit(rng):
    x, rw, gu, dn = _mk_weights(rng)
    want = _dense_oracle(x, rw, gu, dn, 2)
    got = jax.jit(
        lambda *a: moe_ops.routed_moe(*a, 2))(x, rw, gu, dn)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-5, rtol=1e-4)


@pytest.mark.parametrize("ep", [2, 4, 8])
@pytest.mark.slow  # multi-device; the dryrun MoE-EP leg covers this
def test_routed_ep_matches_dense(rng, devices8, ep):
    x, rw, gu, dn = _mk_weights(rng, e=8, t=16)
    want = _dense_oracle(x, rw, gu, dn, 2)
    mesh = build_mesh(
        MeshConfig(expert_parallel_size=ep, data_parallel_size=8 // ep),
        devices8)
    got = moe_ops.routed_moe_ep(x, rw, gu, dn, 2, mesh)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-5, rtol=1e-4)


@pytest.mark.slow  # heavy compile; routed-vs-dense already covered at op level
def test_transformer_forward_routed_matches_dense(rng, devices8):
    """forward_hidden with moe_dispatch=routed (incl. EP via set_ep_mesh)
    matches the dense-dispatch forward token-for-token."""
    cfg_dense = dataclasses.replace(
        tfm.TransformerConfig.tiny_moe(), moe_dispatch="dense")
    cfg_routed = dataclasses.replace(cfg_dense, moe_dispatch="routed")
    params = tfm.init_params(jax.random.PRNGKey(0), cfg_dense, jnp.float32)
    ids = jax.random.randint(rng, (2, 10), 0, cfg_dense.vocab_size)

    want = tfm.forward_hidden(params, cfg_dense, ids)
    got_local = tfm.forward_hidden(params, cfg_routed, ids)
    np.testing.assert_allclose(np.asarray(got_local), np.asarray(want),
                               atol=2e-5, rtol=1e-4)

    mesh = build_mesh(
        MeshConfig(expert_parallel_size=4, data_parallel_size=2), devices8)
    moe_ops.set_ep_mesh(mesh)
    try:
        from vllm_omni_tpu.parallel.sharding import shard_moe_params

        sharded = shard_moe_params(params, mesh)
        got_ep = tfm.forward_hidden(sharded, cfg_routed, ids)
    finally:
        moe_ops.set_ep_mesh(None)
    np.testing.assert_allclose(np.asarray(got_ep), np.asarray(want),
                               atol=2e-5, rtol=1e-4)


# ---- the chip's share of an expert-parallel deployment (ISSUE 28)
def _shared_expert_layer(rng, hidden=16, e=8, inter=8, shared=8):
    ks = jax.random.split(rng, 6)
    return {
        "router": {"w": jax.random.normal(ks[0], (hidden, e)) * 0.5},
        "experts": {
            "gate_up": jax.random.normal(ks[1], (e, hidden, 2 * inter)) * 0.2,
            "down": jax.random.normal(ks[2], (e, inter, hidden)) * 0.2},
        "shared_expert": {
            "gate_up": {"w": jax.random.normal(ks[3], (hidden, 2 * shared))
                        * 0.2},
            "down": {"w": jax.random.normal(ks[4], (shared, hidden)) * 0.2}},
        "shared_gate": {"w": jax.random.normal(ks[5], (hidden, 1))},
    }


@pytest.mark.parametrize("dispatch", ["routed", "dense"])
def test_the_four_shares_and_the_shared_expert_add_up_to_the_layer(
        rng, dispatch):
    """The routed parts that four chips give (experts 0-1, 2-3, 4-5, 6-7
    of an 8-wide router, top-2 renormalised over the picks wherever they
    live) plus the shared expert counted once equal the uncut layer.
    float32 throughout: the sums differ by their order only (1e-6)."""
    layer = _shared_expert_layer(rng)
    x = jax.random.normal(jax.random.PRNGKey(5), (24, 16))
    whole_cfg = tfm.TransformerConfig(
        moe=True, num_experts=8, num_experts_per_tok=2,
        shared_expert_size=8, moe_dispatch=dispatch)
    whole = tfm._moe_mlp(layer, whole_cfg, x)
    routed_only = {k: v for k, v in layer.items()
                   if not k.startswith("shared")}
    parts = []
    for offset in (0, 2, 4, 6):
        share = dict(routed_only, experts={
            k: v[offset:offset + 2] for k, v in layer["experts"].items()})
        cfg = dataclasses.replace(whole_cfg, num_experts=2, router_experts=8,
                                  expert_offset=offset)
        parts.append(tfm._moe_mlp(share, cfg, x))
    shared_once = tfm._moe_mlp(layer, whole_cfg, x) - tfm._moe_mlp(
        routed_only, whole_cfg, x)
    np.testing.assert_allclose(np.asarray(sum(parts) + shared_once),
                               np.asarray(whole), atol=2e-6, rtol=1e-5)
    # a share is no stand-in for the layer: alone it gives less
    assert float(jnp.abs(parts[0] + shared_once - whole).max()) > 1e-3


def test_a_share_computes_only_the_pairs_of_its_own_experts(rng):
    """Picks that live elsewhere sort behind the last held expert's group:
    the grouped matmul's group sizes sum to the local pairs, not T * k."""
    x, rw, gu, dn = _mk_weights(rng, t=32, e=8)
    idx, _ = moe_ops.router_topk(x, rw, 2)
    local = int(jnp.sum((idx >= 4) & (idx < 6)))
    seen = {}
    real = moe_ops._grouped_mlp

    def spy(xs, gate_up, down, group_sizes):
        seen["sizes"] = np.asarray(group_sizes)
        return real(xs, gate_up, down, group_sizes)

    moe_ops._grouped_mlp = spy
    try:
        moe_ops.routed_moe_share(x, rw, gu[4:6], dn[4:6], 2, 4)
    finally:
        moe_ops._grouped_mlp = real
    assert seen["sizes"].shape == (2,) and int(seen["sizes"].sum()) == local
    assert 0 < local < 64
