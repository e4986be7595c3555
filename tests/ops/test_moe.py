"""Routed MoE (grouped matmul + EP shard_map) vs the dense-dispatch
oracle (VERDICT r1 weak#4: dense dispatch wastes k/E of the FLOPs; the
routed path must match it exactly).  Reference semantics: vLLM fused MoE
consumed by the Qwen3 thinker/talker (models/qwen3_omni/qwen3_moe.py)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

# ``uncapped_share``: ``routed_moe_share`` as it was until PR 53, which is
# its definition (the chip's sweep times it beside the tree's)
from scripts.moe_share_rows_sweep import (
    plan,
    share_cap,
    share_form,
    uncapped_share,
)
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

    def spy(xs, gate_up, down, group_sizes, *act):
        seen["sizes"] = np.asarray(group_sizes)
        return real(xs, gate_up, down, group_sizes, *act)

    moe_ops._grouped_mlp = spy
    try:
        moe_ops.routed_moe_share(x, rw, gu[4:6], dn[4:6], 2, 4)
    finally:
        moe_ops._grouped_mlp = real
    assert seen["sizes"].shape == (2,) and int(seen["sizes"].sum()) == local
    assert 0 < local < 64


# ------------------------------------------- the router's scoring, as data
def test_sigmoid_router_selects_on_the_bias_and_weighs_without_it():
    """A hand-written case: four experts, top-2, identity router so that
    the logits are the input.  Scores sigmoid(2, 1, 0.5, -3) = 0.881,
    0.731, 0.622, 0.047.  Without a bias the picks are experts 0 and 1.
    A bias of +0.2 on expert 2 lifts it over expert 1 (0.822 > 0.731) and
    changes the PICK; the WEIGHTS are the unbiased scores of the picks,
    0.881 and 0.622 over their sum, times the scaling factor."""
    x = jnp.array([[2.0, 1.0, 0.5, -3.0]])
    w = jnp.eye(4)
    s = 1 / (1 + np.exp(-np.array([2.0, 1.0, 0.5, -3.0])))
    idx, wts = moe_ops.router_topk(x, w, 2, scoring="sigmoid")
    assert idx.tolist() == [[0, 1]]
    np.testing.assert_allclose(wts[0], s[[0, 1]] / s[[0, 1]].sum(),
                               rtol=1e-6)
    bias = jnp.array([0.0, 0.0, 0.2, 0.0])
    idx, wts = moe_ops.router_topk(x, w, 2, scoring="sigmoid", bias=bias,
                                   scale=1.8)
    assert idx.tolist() == [[0, 2]]
    np.testing.assert_allclose(
        wts[0], 1.8 * s[[0, 2]] / s[[0, 2]].sum(), rtol=1e-6)
    # not the biased scores' weights, and not a softmax's
    biased = (s + np.asarray(bias))[[0, 2]]
    assert abs(float(wts[0, 1]) - 1.8 * biased[1] / biased.sum()) > 0.05
    # a bias that changes no pick changes nothing
    same = moe_ops.router_topk(x, w, 2, scoring="sigmoid",
                               bias=jnp.array([0.0, 0.0, 0.05, 0.0]))
    assert same[0].tolist() == [[0, 1]]
    # without renormalisation the raw scores are the weights
    _, raw = moe_ops.router_topk(x, w, 2, renormalize=False,
                                 scoring="sigmoid")
    np.testing.assert_allclose(raw[0], s[[0, 1]], rtol=1e-6)
    with pytest.raises(ValueError, match="softmax or sigmoid"):
        moe_ops.router_topk(x, w, 2, scoring="tanh")


def test_the_default_router_is_the_softmax_it_was():
    rs = np.random.default_rng(0)
    x = jnp.asarray(rs.standard_normal((6, 16)), jnp.float32)
    w = jnp.asarray(rs.standard_normal((16, 8)), jnp.float32)
    idx, wts = moe_ops.router_topk(x, w, 3)
    probs = jax.nn.softmax(x @ w, -1)
    top_w, top_i = jax.lax.top_k(probs, 3)
    assert idx.tolist() == top_i.tolist()
    np.testing.assert_allclose(wts, top_w / top_w.sum(-1, keepdims=True),
                               rtol=1e-6)


def test_routed_moe_with_a_sigmoid_router_equals_the_dense_oracle():
    """The grouped matmul under the sigmoid / bias / scale router against
    every expert computed and masked by the same router's weights, and a
    layer's MLP kind read from its weights (dense first, sparse after,
    the shared expert added whole)."""
    import dataclasses

    from vllm_omni_tpu.models.common import transformer as tfm
    from vllm_omni_tpu.models.glm4_moe_lite import tiny_config

    cfg = tiny_config()
    params = tfm.init_params(jax.random.PRNGKey(2), cfg, jnp.float32)
    dense_layer, sparse_layer = params["layers"][0], params["layers"][1]
    x = jax.random.normal(jax.random.PRNGKey(3), (11, cfg.hidden_size))
    routed = tfm._mlp(sparse_layer, cfg, x)
    oracle = tfm._mlp(sparse_layer,
                      dataclasses.replace(cfg, moe_dispatch="dense"), x)
    np.testing.assert_allclose(routed, oracle, atol=2e-5)
    # by hand: the picks of s + b, weighted by s, scaled, plus the shared
    s = jax.nn.sigmoid(x @ sparse_layer["router"]["w"])
    _, picks = jax.lax.top_k(s + sparse_layer["router"]["e_bias"], 2)
    wts = jnp.take_along_axis(s, picks, -1)
    wts = 1.8 * wts / wts.sum(-1, keepdims=True)

    def expert(gate_up, down, h):
        gate, up = jnp.split(h @ gate_up, 2, -1)
        return (jax.nn.silu(gate) * up) @ down

    want = expert(sparse_layer["shared_expert"]["gate_up"]["w"],
                  sparse_layer["shared_expert"]["down"]["w"], x)
    for t in range(x.shape[0]):
        for j in range(2):
            e = int(picks[t, j])
            want = want.at[t].add(wts[t, j] * expert(
                sparse_layer["experts"]["gate_up"][e],
                sparse_layer["experts"]["down"][e], x[t]))
    np.testing.assert_allclose(routed, want, atol=2e-5)
    # the first layer holds no experts: a plain SwiGLU of 160
    np.testing.assert_allclose(
        tfm._mlp(dense_layer, cfg, x),
        expert(dense_layer["gate_up"]["w"], dense_layer["down"]["w"], x),
        atol=2e-5)


# ---- a share and an ep shard under a sigmoid router (ISSUE 34: the
# refusal of PR 32 is gone; these hold what replaced it)
def _sigmoid_layer(rng):
    layer = _shared_expert_layer(rng)
    del layer["shared_gate"]  # added whole, as the noaux_tc models do
    layer["router"]["e_bias"] = jax.random.uniform(
        jax.random.PRNGKey(11), (8,), jnp.float32, minval=-0.2, maxval=0.2)
    return layer


_SIGMOID = dict(moe=True, num_experts=8, num_experts_per_tok=2,
                shared_expert_size=8, shared_expert_gate=False,
                router_scoring="sigmoid", router_bias=True,
                routed_scaling_factor=1.5)


@pytest.mark.parametrize("dispatch", ["routed", "dense"])
def test_the_shares_of_a_sigmoid_router_add_up_to_the_layer(rng, dispatch):
    """The share test of the model-configs guide under the sigmoid router
    with its correction bias and a scaling factor: the routed parts four
    chips give (two experts each of an 8-wide router: selected on s + b,
    weighted by s renormalised over both picks wherever they live, times
    1.5) and the shared expert counted once equal the uncut layer."""
    layer = _sigmoid_layer(rng)
    x = jax.random.normal(jax.random.PRNGKey(5), (24, 16))
    whole_cfg = tfm.TransformerConfig(**_SIGMOID, moe_dispatch=dispatch)
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
    shared_once = whole - tfm._moe_mlp(routed_only, whole_cfg, x)
    np.testing.assert_allclose(np.asarray(sum(parts) + shared_once),
                               np.asarray(whole), atol=2e-6, rtol=1e-5)
    assert float(jnp.abs(parts[0] + shared_once - whole).max()) > 1e-3
    # the bias selects: without it another pair of experts answers
    no_bias = dict(layer, router={"w": layer["router"]["w"]})
    plain = tfm._moe_mlp(no_bias, dataclasses.replace(
        whole_cfg, router_bias=False), x)
    assert float(jnp.abs(plain - whole).max()) > 1e-3


def test_the_ep_shards_route_as_the_sigmoid_router_does(rng, devices8):
    """The psum expert-parallel dispatch under the same router equals the
    one-shard layer; the all-to-all dispatch says that it does not take
    one."""
    layer = _sigmoid_layer(rng)
    x = jax.random.normal(jax.random.PRNGKey(6), (16, 16))
    cfg = tfm.TransformerConfig(**_SIGMOID)
    want = tfm._moe_mlp(layer, cfg, x)
    moe_ops.set_ep_mesh(build_mesh(
        MeshConfig(expert_parallel_size=4, data_parallel_size=2), devices8))
    got = tfm._moe_mlp(layer, cfg, x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-6, rtol=1e-5)
    with pytest.raises(NotImplementedError, match="all-to-all"):
        tfm._moe_mlp(layer, dataclasses.replace(cfg, moe_dispatch="a2a"), x)


# ---- the combine (ISSUE 43): a gather by the sort's inverse and a float32
# sum over a token's k picks, where a scatter-add of T*k rows stood
def _scatter_add_combine(y, order, topk_w, held=None):
    """What ended ``routed_moe`` / ``routed_moe_share`` until PR 43."""
    t, k = topk_w.shape
    w = topk_w.reshape(-1)[order]
    y = y * w[:, None].astype(y.dtype)
    if held is not None:
        y = jnp.where(held.reshape(-1)[order][:, None], y, 0)
    return jnp.zeros((t, y.shape[1]), y.dtype).at[order // k].add(y)


def _sorted_pairs(t, k, h, width, e_held, seed=0):
    """(y, order, topk_w, held, live rows): a router's picks over ``width``
    experts of which the first ``e_held`` are here, sorted as
    ``routed_moe_share`` sorts them."""
    rs = np.random.default_rng(seed)
    idx = np.stack([rs.permutation(width)[:k] for _ in range(t)])
    topk_w = rs.uniform(0.1, 1.0, (t, k)).astype(np.float32)
    held = idx < e_held
    order = np.argsort(np.where(held, idx, e_held).reshape(-1),
                       kind="stable").astype(np.int32)
    y = rs.standard_normal((t * k, h)).astype(np.float32)
    return (jnp.asarray(y), jnp.asarray(order), jnp.asarray(topk_w),
            jnp.asarray(held), int(held.sum()))


@pytest.mark.parametrize("t,k,h,width,e_held", [
    (12, 2, 16, 4, 4), (5, 1, 16, 4, 4), (1, 4, 16, 8, 8), (1, 1, 8, 2, 2),
    (64, 8, 32, 64, 8), (24, 10, 16, 72, 18), (7, 3, 16, 5, 0)],
    ids=["t12k2", "k1", "t1", "t1k1", "share_1_in_8", "share_1_in_4",
         "share_none_live"])
def test_combine_equals_the_scatter_add_it_replaces(t, k, h, width, e_held):
    y, order, topk_w, held, _ = _sorted_pairs(t, k, h, width, e_held)
    mask = None if e_held == width else held
    got = jax.jit(moe_ops._combine)(y, order, topk_w, mask)
    assert got.shape == (t, h) and got.dtype == jnp.float32
    np.testing.assert_allclose(
        np.asarray(got),
        np.asarray(_scatter_add_combine(y, order, topk_w, mask)),
        atol=2e-6, rtol=1e-5)
    if e_held == 0:
        assert not np.asarray(got).any()


def test_a_share_masks_the_value_of_an_absent_pick_not_its_weight():
    """The rows of ``y`` behind the last held group are whatever the
    grouped matmul left there.  Poisoned with NaN they must not reach the
    sum: ``NaN * 0`` is ``NaN``, so the mask is on the value."""
    y, order, topk_w, held, live = _sorted_pairs(64, 8, 32, 64, 8)
    assert 0 < live < 64 * 8
    want = moe_ops._combine(y, order, topk_w, held)
    got = moe_ops._combine(y.at[live:].set(jnp.nan), order, topk_w, held)
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    # and a zero weight alone would not have done
    zero_w = jnp.where(held, topk_w, 0.0)
    assert np.isnan(np.asarray(moe_ops._combine(
        y.at[live:].set(jnp.nan), order, zero_w))).any()


def test_a_share_whose_absent_rows_are_poison_gives_what_it_gave(rng):
    """The same through ``routed_moe_share``: the grouped matmul's rows
    past its group sizes overwritten with NaN change nothing."""
    x, rw, gu, dn = _mk_weights(rng, t=32, e=8)
    want = moe_ops.routed_moe_share(x, rw, gu[4:6], dn[4:6], 2, 4)
    real = moe_ops._grouped_mlp

    def poisoned(xs, gate_up, down, group_sizes, *act):
        rows = jnp.arange(xs.shape[0])[:, None]
        return jnp.where(rows < group_sizes.sum(),
                         real(xs, gate_up, down, group_sizes, *act), jnp.nan)

    moe_ops._grouped_mlp = poisoned
    try:
        got = moe_ops.routed_moe_share(x, rw, gu[4:6], dn[4:6], 2, 4)
    finally:
        moe_ops._grouped_mlp = real
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert float(jnp.abs(want).max()) > 1e-3


# ---- the share against its definition (ISSUE 53): the absent pairs' rows
# are masked by their place in the sorted order, and one sort carries each
# pair's token and weight there; ``uncapped_share`` is the function as it
# was, which gathered the mask and the weights by the sort's order
def _steered(rng, k, lift, to=(4, 8), t=64, e=16, hidden=16, inter=8):
    """Weights of a 16-wide router whose experts ``to`` get ``lift`` added
    to their logit on every token (``x``'s first column is 1): +50 sends
    every pick there, -50 none, 0 leaves the router as drawn."""
    x, rw, gu, dn = _mk_weights(rng, t=t, hidden=hidden, e=e, inter=inter)
    x = x.at[:, 0].set(1.0)
    rw = rw.at[0, to[0]:to[1]].add(lift)
    return x, rw, gu, dn


def _held(x, rw, k, offset, e_held, **router):
    """(held pairs, each held group's (start, end) in the sorted order) of
    the share at ``offset``."""
    idx, _ = moe_ops.router_topk(x, rw, k, **router)
    sizes = np.bincount(np.asarray(idx).reshape(-1) - offset + e_held,
                        minlength=3 * e_held)[e_held:2 * e_held]
    ends = np.cumsum(sizes)
    return int(ends[-1]), list(zip(ends - sizes, ends))


#: every form of the cut that PR 53 tried (scripts/moe_share_rows_sweep.py):
#: all exact here; on the chip each stopped dots3's 2 048-token step
CUTS = ("windows", "first_then_windows", "static_windows", "one_window",
        "cond")


@pytest.mark.parametrize("k,lift,to", [
    (4, 0.0, (4, 8)), (4, 50.0, (4, 8)), (4, -50.0, (4, 8)),
    (2, 50.0, (4, 6))],
    ids=["uniform", "every_pick_held", "no_pick_held",
         "cap_splits_a_group"])
@pytest.mark.parametrize("form", ("tree", "tree_jit") + CUTS)
def test_a_share_equals_the_share_as_it_was(rng, form, k, lift, to):
    """Experts 4-7 of sixteen, 64 tokens.  Whatever the router does the
    share is its definition: under a uniform router, where every pick is
    held (the sorted order has no absent row to mask, and a cut works on
    the rows behind its ``cap`` too: no pair dropped), where none is
    (zeros, and nothing read from rows nobody wrote), and where a cut's
    ``cap`` falls inside an expert's group."""
    x, rw, gu, dn = _steered(rng, k, lift, to)
    held, groups = _held(x, rw, k, 4, 4)
    cap = share_cap(64 * k, 4, 16)
    assert cap == 24 * k < 64 * k
    assert held == {50.0: 64 * k, -50.0: 0}.get(lift, held)
    if to == (4, 6):
        assert held > cap and any(lo < cap < hi for lo, hi in groups)
    share = share_form(form.removesuffix("_jit"))

    def call(*a):
        return share(*a, k, 4)

    got = (jax.jit(call) if form == "tree_jit" else call)(
        x, rw, gu[4:8], dn[4:8])
    want = uncapped_share(x, rw, gu[4:8], dn[4:8], k, 4)
    assert got.shape == x.shape and got.dtype == x.dtype
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-6, rtol=1e-5)
    if lift < 0:
        assert not np.asarray(got).any()
    else:
        assert float(jnp.abs(want).max()) > 1e-3


@pytest.mark.parametrize("form", ["ungated_latent", "sigmoid_bias"])
def test_a_share_takes_every_router_and_expert_form_as_it_did(rng, form):
    """Ungated experts on a latent row the router does not read, and a
    sigmoid router whose correction bias sends every pick to the held
    experts: the share is still its definition."""
    from vllm_omni_tpu.ops.activation import relu2

    x, rw, gu, dn = _steered(rng, 4, 0.0)
    if form == "ungated_latent":
        h = jax.random.normal(jax.random.PRNGKey(9), (64, 24))
        rw = jax.random.normal(jax.random.PRNGKey(10), (24, 16)) * 0.5
        kw = dict(act=relu2, route_on=h)
        gu = gu[:, :, :8]  # the experts' ``up`` alone
    else:
        bias = jnp.zeros((16,)).at[4:8].set(2.0)
        kw = dict(scoring="sigmoid", bias=bias, scale=1.8)
        idx, _ = moe_ops.router_topk(x, rw, 4, **kw)
        assert bool(jnp.all((idx >= 4) & (idx < 8)))
    got = moe_ops.routed_moe_share(x, rw, gu[4:8], dn[4:8], 4, 4, **kw)
    want = uncapped_share(x, rw, gu[4:8], dn[4:8], 4, 4, **kw)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-6, rtol=1e-5)
    assert float(jnp.abs(want).max()) > 1e-3


def test_a_share_gathers_no_scalar_by_the_sorts_order(rng):
    """The two passes over all T * k pairs that PR 53 took out: the mask
    and the weights were gathered by ``argsort``'s order, at 10 ns a
    scalar on the chip.  The gathers left are
    of rows: the tokens' (``x[token_of]``), and ``router_topk``'s own."""
    x, rw, gu, dn = _mk_weights(rng, t=32, e=8)
    jaxpr = jax.make_jaxpr(
        lambda *a: moe_ops.routed_moe_share(*a, 2, 4))(x, rw, gu[4:6],
                                                       dn[4:6])
    flat = [e for e in _primitives(jaxpr.jaxpr)
            if e.primitive.name == "gather" and e.outvars[0].aval.ndim == 1]
    assert not flat, flat
    was = jax.make_jaxpr(
        lambda *a: uncapped_share(*a, 2, 4))(x, rw, gu[4:6], dn[4:6])
    assert len([e for e in _primitives(was.jaxpr)
                if e.primitive.name == "gather"
                and e.outvars[0].aval.ndim == 1]) == 2


@pytest.mark.parametrize("pairs,e_held,width,cap", [
    (20480, 128, 512, 7680), (16384, 32, 256, 3072), (45056, 128, 512, 16896),
    (20480, 18, 72, 7680), (80, 128, 512, 32), (64, 32, 256, 16),
    (8, 2, 8, 8), (256, 4, 16, 96), (64, 8, 8, 64)],
    ids=["qwen3next_2048", "dots3_2048", "nemotron_2048", "granite_2048",
         "ep4_8_tokens", "ep8_8_tokens", "never_over_the_pairs", "tiny",
         "every_expert_held"])
def test_a_cuts_cap_is_the_expected_pairs_and_a_half_in_whole_tiles(
        pairs, e_held, width, cap):
    assert share_cap(pairs, e_held, width) == cap


@pytest.mark.parametrize("cell,caps", [
    ("qwen3_next", (2, 4, 8, 16, 32, 48)),
    ("dots3note", (2, 4, 8, 16, 32, 48)),
    ("granite4h", (3, 6, 12, 48, 80, 144)),
    ("nemotron3s", (3, 6, 12, 48, 80, 144)),
    ("commandaplus", (2, 4, 8, 32, 48, 96))])
def test_the_plan_of_a_tiny_share(cell, caps):
    """``scripts.moe_share_rows_sweep --plan`` on the tiny cell of each of
    the five share configurations (steps of 1, 2, 4, 16, 32 and 64 rows):
    a cut's ``cap`` is the pairs where they are few, then 1.5 times the
    held experts' part of them in whole sublane tiles, and off the
    kernel's tiling both products keep ``ragged_dot`` with and without."""
    import importlib

    tiny = importlib.import_module(f"tests.benchmark.test_{cell}_cell").TINY
    lines = plan(tiny)
    assert [ln["rows"] for ln in lines] == [1, 2, 4, 16, 32, 64]
    assert tuple(ln["cap"] for ln in lines) == caps
    assert all(ln["cap"] <= ln["pairs"] for ln in lines)
    assert lines[-1]["cap"] < lines[-1]["pairs"]
    assert not any(ln["changed"] for ln in lines)


def test_the_sum_over_a_tokens_picks_is_float32():
    """One token, eight picks of weight 1: 256 and seven ones.  A running
    sum in bfloat16 never leaves 256 (257 is no bfloat16 and ties go to
    the even neighbour); the float32 sum is 263, which rounds once, to
    264."""
    y = jnp.asarray([[256.0]] + [[1.0]] * 7, jnp.bfloat16)
    order = jnp.arange(8, dtype=jnp.int32)
    topk_w = jnp.ones((1, 8), jnp.float32)
    got = moe_ops._combine(y, order, topk_w)
    assert got.dtype == jnp.float32 and float(got[0, 0]) == 263.0
    assert float(got.astype(jnp.bfloat16)[0, 0]) == 264.0
    assert float(_scatter_add_combine(y, order, topk_w)[0, 0]) == 256.0


def _primitives(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _primitives(sub)


def test_no_scatter_add_of_rows_is_left_in_routed_moe(rng):
    """``routed_moe`` scatter-adds activations no more: the one
    ``scatter-add`` left in it is ``bincount``'s, over int32 counts.  (The
    share still ends in one: every form of ``_combine`` tried in its place
    stopped dots3-note-prev's 2 048-row step on the chip: PERF.md, PR 43.)"""
    x, rw, gu, dn = _mk_weights(rng, t=32, e=8)
    jaxpr = jax.make_jaxpr(
        lambda *a: moe_ops.routed_moe(*a, 2))(x, rw, gu, dn)
    adds = [e for e in _primitives(jaxpr.jaxpr)
            if e.primitive.name == "scatter-add"]
    assert all(e.outvars[0].aval.ndim == 1
               and jnp.issubdtype(e.outvars[0].aval.dtype, jnp.integer)
               for e in adds), adds
    assert len(adds) <= 1
