"""Ungated experts in a latent (Nemotron-H's ``E`` sublayer): the routed
dispatch, an expert share and the dense oracle against each other and
against the layer's equations written out here; the four shares, each
through ``W_2``, and the shared expert counted once add up to the uncut
layer; and a router whose logits are rounded to bfloat16 picks other
experts, which the tolerance sees.

    s = sigmoid(h W_g); the k largest of s + bias are picked
    w_i = scale * s_i / sum_picked s
    l = h W_1;  r = sum_i w_i D_i relu(U_i l)^2
    out = r W_2 + D_s relu(U_s h)^2
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from vllm_omni_tpu.models.common import transformer as tfm
from vllm_omni_tpu.ops import moe as moe_ops
from vllm_omni_tpu.ops.activation import relu2

HIDDEN, LATENT, INTER, SHARED, E, K, SCALE = 32, 8, 12, 24, 8, 3, 2.5
CFG = tfm.TransformerConfig(
    hidden_size=HIDDEN, moe=True, num_experts=E, num_experts_per_tok=K,
    moe_intermediate_size=INTER, shared_expert_size=SHARED,
    shared_expert_gate=False, router_scoring="sigmoid", router_bias=True,
    routed_scaling_factor=SCALE, mlp_gated=False, mlp_activation="relu2",
    moe_latent_size=LATENT)
# float32 throughout: the forms differ by the order of their sums (the
# grouped matmul against an einsum over every expert, k rows summed against
# a masked sum over E), a few roundings on values of order 1.  A pick
# swapped for another expert reads 1e-2 and more.
TOL = dict(atol=5e-6, rtol=2e-5)


def _layer(key):
    """An ``E`` sublayer's weights as ``init_params`` lays them out."""
    layer = tfm._mlp_init(jax.random.split(key, 8), CFG, True, jnp.float32)
    assert sorted(layer) == ["experts", "fc1_latent_proj", "fc2_latent_proj",
                             "router", "shared_expert"]
    assert sorted(layer["experts"]) == ["down", "up"]
    assert layer["experts"]["up"].shape == (E, LATENT, INTER)
    assert layer["experts"]["down"].shape == (E, INTER, LATENT)
    assert sorted(layer["shared_expert"]) == ["down", "up"]
    assert layer["shared_expert"]["up"]["w"].shape == (HIDDEN, SHARED)
    # the plain draw's correction bias is small: here it decides picks
    layer["router"]["e_bias"] = layer["router"]["e_bias"] * 4.0
    return layer


def _equations(layer, h, held=slice(0, E)):
    """The docstring's equations in numpy, float64, a token at a time; the
    routed sum over the picked experts that lie in ``held``."""
    f = lambda a: np.asarray(a, np.float64)  # noqa: E731
    h = f(h)
    scores = 1.0 / (1.0 + np.exp(-(h @ f(layer["router"]["w"]))))
    biased = scores + f(layer["router"]["e_bias"])
    latent = h @ f(layer["fc1_latent_proj"]["w"])
    up, down = f(layer["experts"]["up"]), f(layer["experts"]["down"])
    out = np.zeros_like(h)
    for t in range(h.shape[0]):
        picked = np.argsort(-biased[t])[:K]
        total = scores[t, picked].sum()
        r = np.zeros(LATENT)
        for e in picked:
            if held.start <= e < held.stop:
                act = np.maximum(latent[t] @ up[e], 0.0) ** 2
                r += SCALE * scores[t, e] / total * (act @ down[e])
        out[t] = r @ f(layer["fc2_latent_proj"]["w"])
    shared = layer["shared_expert"]
    return out, (np.maximum(h @ f(shared["up"]["w"]), 0.0) ** 2) \
        @ f(shared["down"]["w"])


@pytest.mark.parametrize("dispatch", ["routed", "dense"])
def test_the_layer_is_its_equations(rng, dispatch):
    layer = _layer(rng)
    h = jax.random.normal(jax.random.PRNGKey(3), (24, HIDDEN))
    routed, shared = _equations(layer, h)
    got = tfm._moe_mlp(layer, dataclasses.replace(CFG, moe_dispatch=dispatch),
                       h)
    np.testing.assert_allclose(got, routed + shared, **TOL)
    # the latent matters: experts fed the model's own row are refused by
    # their shapes, and a gated reading of ``up`` is not what runs
    assert float(np.abs(routed).max()) > 0.05 < float(np.abs(shared).max())


def test_routed_share_and_dense_agree_ungated_in_the_latent(rng):
    """``ops/moe.py``'s three forms told ``act`` and ``route_on``: the
    router reads the model's row, the experts its latent, whose width the
    result keeps."""
    layer = _layer(rng)
    h = jax.random.normal(jax.random.PRNGKey(4), (40, HIDDEN))
    latent = h @ layer["fc1_latent_proj"]["w"]
    router = dict(scoring="sigmoid", bias=layer["router"]["e_bias"],
                  scale=SCALE, float32_logits=True)
    up, down = layer["experts"]["up"], layer["experts"]["down"]
    whole = moe_ops.routed_moe(latent, layer["router"]["w"], up, down, K,
                               act=relu2, route_on=h, **router)
    assert whole.shape == (40, LATENT)
    dense = tfm._moe_mlp_dense(layer, CFG, h, latent)
    np.testing.assert_allclose(whole, dense, **TOL)
    parts = [moe_ops.routed_moe_share(
        latent, layer["router"]["w"], up[lo:lo + 2], down[lo:lo + 2], K, lo,
        act=relu2, route_on=h, **router) for lo in range(0, E, 2)]
    np.testing.assert_allclose(sum(parts), whole, **TOL)
    # ungated is not gated: the same tensors read as [gate | up] differ
    gated = moe_ops.routed_moe(latent, layer["router"]["w"], up, down[:, :6],
                               K, route_on=h, **router)
    assert float(jnp.abs(gated - whole).max()) > 1e-2
    # and the router reads h: routed on the latent's own rows (a matrix of
    # the latent's width) other experts answer
    own = moe_ops.routed_moe(latent, layer["router"]["w"][:LATENT], up, down,
                             K, act=relu2, **router)
    assert float(jnp.abs(own - whole).max()) > 1e-2


@pytest.mark.parametrize("dispatch", ["routed", "dense"])
def test_the_four_shares_through_w2_and_the_shared_expert_add_up(
        rng, dispatch):
    """The share is the model's (model-configs guide, section 4): the
    routed parts that four chips give (experts at offsets 0, E/4, E/2 and
    3E/4 of an 8-wide router, the picks renormalised wherever they live),
    EACH brought out of the latent through ``W_2`` on its own chip, plus
    the shared expert counted once, equal the uncut layer as the equations
    give it.  ``W_2`` is linear and has no bias, so the shares add up."""
    layer = _layer(rng)
    h = jax.random.normal(jax.random.PRNGKey(5), (24, HIDDEN))
    routed, shared = _equations(layer, h)
    whole_cfg = dataclasses.replace(CFG, moe_dispatch=dispatch)
    routed_only = {k: v for k, v in layer.items() if k != "shared_expert"}
    parts = []
    for offset in range(0, E, E // 4):
        share = dict(routed_only, experts={
            k: v[offset:offset + E // 4]
            for k, v in layer["experts"].items()})
        cfg = dataclasses.replace(whole_cfg, num_experts=E // 4,
                                  router_experts=E, expert_offset=offset)
        part = tfm._moe_mlp(share, cfg, h)
        want, _ = _equations(layer, h, slice(offset, offset + E // 4))
        np.testing.assert_allclose(part, want, **TOL)
        parts.append(part)
    shared_once = tfm._dense_mlp(layer["shared_expert"], CFG, h)
    np.testing.assert_allclose(shared_once, shared, **TOL)
    np.testing.assert_allclose(sum(parts) + shared_once, routed + shared,
                               **TOL)
    # a share is no stand-in for the layer: alone it gives less
    assert float(np.abs(parts[0] - routed).max()) > 1e-2


def test_router_logits_rounded_to_bfloat16_fail_the_tolerance(rng):
    """The control: the same layer with the router's logits rounded to
    bfloat16 before the sigmoid (``float32_logits`` off on bfloat16 rows'
    product) swaps marginal picks of some tokens, and those tokens read
    far outside ``TOL``."""
    layer = _layer(rng)
    h = jax.random.normal(jax.random.PRNGKey(6), (256, HIDDEN))
    latent = h @ layer["fc1_latent_proj"]["w"]
    router = dict(scoring="sigmoid", bias=layer["router"]["e_bias"],
                  scale=SCALE)
    up, down = layer["experts"]["up"], layer["experts"]["down"]
    whole = moe_ops.routed_moe(latent, layer["router"]["w"], up, down, K,
                               act=relu2, route_on=h, float32_logits=True,
                               **router)
    logits = (h @ layer["router"]["w"]).astype(jnp.bfloat16)
    # the router's matrix as the identity over rounded logits
    rounded = moe_ops.routed_moe(
        latent, jnp.eye(E), up, down, K, act=relu2,
        route_on=logits.astype(jnp.float32), float32_logits=True, **router)
    diff = np.abs(np.asarray(rounded - whole)).max(axis=-1)
    assert (diff > 1e-2).sum() >= 1          # a swapped pick, at full size
    assert (diff < 1e-4).sum() >= 128        # most tokens: the same picks
    with pytest.raises(AssertionError):
        np.testing.assert_allclose(rounded, whole, **TOL)


def test_ungated_or_latent_experts_over_an_ep_mesh_are_refused(rng,
                                                               devices8):
    from vllm_omni_tpu.parallel.mesh import MeshConfig, build_mesh

    layer = _layer(rng)
    moe_ops.set_ep_mesh(build_mesh(MeshConfig(expert_parallel_size=2),
                                   devices8[:2]))
    try:
        with pytest.raises(NotImplementedError, match="ep mesh"):
            tfm._moe_mlp(layer, CFG, jnp.zeros((4, HIDDEN)))
    finally:
        moe_ops.set_ep_mesh(None)


def test_a_gated_layer_is_told_nothing_new(rng):
    """A gated, full-width layer's dispatch gets no ``act`` and no
    ``route_on``: the program every other model runs is the one it ran."""
    seen = {}
    real = moe_ops.routed_moe

    def spy(*args, **kwargs):
        seen.update(kwargs)
        return real(*args, **kwargs)

    cfg = tfm.TransformerConfig.tiny_moe()
    layer = tfm._mlp_init(jax.random.split(rng, 8), cfg, True, jnp.float32)
    assert sorted(layer["experts"]) == ["down", "gate_up"]
    moe_ops.routed_moe = spy
    try:
        tfm._moe_mlp(layer, cfg, jnp.ones((4, cfg.hidden_size)))
    finally:
        moe_ops.routed_moe = real
    assert set(seen) == {"renormalize"}
