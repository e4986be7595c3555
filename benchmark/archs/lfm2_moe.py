"""The LFM2-8B-A1B decoder (``model_type`` ``lfm2_moe``), as a configuration
file names it: ``"arch": "benchmark.archs.lfm2_moe"``.  It keeps the
contract of ``benchmark/archs/qwen3_moe.py`` (``program_config``,
``init_weights``, ``param_specs``, ``reference_logprobs``, ``step_cost``,
``attn_cost``, ``moe_cost``) and adds ``shortconv_cost`` for the roofline of
the gated short convolutions.

The model (x a row of ``hidden_size``; ``rms(x, w) = x * rsqrt(mean(x^2) +
norm_eps) * w``, plain weight).  Every layer is the block ``x = x +
mixer(rms(x, w_op)); x = x + mlp(rms(x, w_ffn))``; after the last layer
``rms(x, w_final)`` (the checkpoint's ``embedding_norm``), then the head,
which is the embedding transposed.  ``layer_types`` says which mixer:

*Gated short convolution* (``"conv"``).  ``[B | C | u] = x W_in`` (hidden ->
3 x hidden, no bias, split in that order); ``v = B * u``; ``c_t = sum_j
w[j] * v_{t - (K - 1) + j}`` a channel (depth-wise, causal, ``conv_L_cache``
K = 3 taps, zeros before a sequence's first token, no bias, no
activation); ``y = C * c``; ``out = y W_out``.  All a sequence carries is
``v`` of its last two tokens.

*Attention* (``"full_attention"``).  ``q = x W_q`` (H heads of D =
hidden / H = 64), ``k = x W_k``, ``v = x W_v`` (Hkv heads), no biases; q
and k take ``rms`` over the head with a weight of their own each, then
rotary (rotate-half over all D dims, ``rope_theta``); causal softmax at
``D^-0.5``, H / Hkv query heads to a key-value head; ``W_o``.

*MLP.*  Layers below ``num_dense_layers``: ``w2(silu(w1 x) * w3 x)`` at
``intermediate_size``.  The others: ``s = sigmoid(x W_r)`` over the experts
in float32; the ``num_experts_per_tok`` largest of ``s + b``
(``use_expert_bias``: a learned float32 vector, for the selection alone);
weights ``s`` of those, divided by their sum (``norm_topk_prob``; no
epsilon is added to the sum, served and referred alike: four sigmoid
scores sum to tenths, where 1e-6 is a rounding), times
``routed_scaling_factor``; ``y = sum_e p_e w2_e(silu(w1_e x) * w3_e x)`` at
``moe_intermediate_size``.  No shared expert.

The reference is straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``: the convolution as a sum of
three shifted products over the whole sequence, attention as a dense masked
softmax (computed a block of query rows at a time so that the check's
4.6k-token prompt fits beside a full chip: the same numbers), every expert
computed for every token and weighted by the router, no cache, no kernel,
no batching.  It shares only the names of the weight tree with the program
(whose modules are imported inside the builder functions alone); the sizes
come from the configuration file's keys.

Departures from the published model, each under the file's ``assumed``:
``tie_word_embeddings`` (the catalog's config has no such key; the family
ties them), no epsilon in the renormalisation, rotary pairing by halves.
"""

from __future__ import annotations

import dataclasses
import functools
import importlib

import jax
import jax.numpy as jnp

from benchmark.archs import glm4_moe_lite


# ---------------------------------------------------------------- builder
def _layer_mix(config: dict) -> dict:
    """Layers of each kind among the file's ``num_hidden_layers``, in the
    mix its ``stage_layers`` state (the pipeline stage served here: nine
    convolutions to three attentions, two dense MLPs and ten sparse).  At
    the file's own depth these are its layers; the counts of a model twice
    as deep are those of two such stages, so that every count of the
    layers is linear in depth, as the harness's contract has it."""
    stage = config["stage_layers"]
    share = config["num_hidden_layers"] / (stage["conv"] + stage["attention"])
    return {k: v * share for k, v in stage.items()}


def program_config(config: dict):
    """The published keys of a configuration file -> the program's
    ``TransformerConfig``.  Where the file names the program's own constant
    for this model (``program_config``), the two must agree on everything
    but depth (the file's layers are the constant's first ones): a width
    that drifted in the program is an error here."""
    from vllm_omni_tpu.models.common.transformer import TransformerConfig

    for key, want in (("conv_bias", False), ("use_expert_bias", True)):
        if config[key] != want:
            raise SystemExit(f"benchmark: {key} {config[key]!r} is not "
                             f"built (only {want!r})")
    kinds = tuple(config["layer_types"])
    cfg = TransformerConfig(
        vocab_size=config["vocab_size"],
        hidden_size=config["hidden_size"],
        num_layers=config["num_hidden_layers"],
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        head_dim=config["hidden_size"] // config["num_attention_heads"],
        intermediate_size=config["intermediate_size"],
        rope_theta=float(config["rope_theta"]),
        rms_eps=config["norm_eps"],
        qk_norm=True,
        tie_word_embeddings=bool(config["tie_word_embeddings"]),
        moe=True,
        num_experts=config["num_experts"],
        num_experts_per_tok=config["num_experts_per_tok"],
        moe_intermediate_size=config["moe_intermediate_size"],
        moe_renormalize=bool(config["norm_topk_prob"]),
        first_k_dense_replace=config["num_dense_layers"],
        router_scoring="sigmoid",
        router_bias=True,
        routed_scaling_factor=float(config["routed_scaling_factor"]),
        layer_kinds=kinds,
        shortconv_kernel=config["conv_L_cache"],
    )
    stage = config["stage_layers"]
    served = {"conv": kinds.count("conv"),
              "attention": kinds.count("full_attention"),
              "dense": cfg.first_k_dense_replace,
              "sparse": cfg.num_layers - cfg.first_k_dense_replace}
    if len(kinds) != cfg.num_layers or served != stage:
        raise SystemExit(f"benchmark: stage_layers {stage} are not the "
                         f"{cfg.num_layers} layers served, {served}")
    if config.get("program_config"):
        module, _, name = config["program_config"].partition(":")
        theirs = getattr(importlib.import_module(module), name)
        theirs = dataclasses.replace(
            theirs, num_layers=cfg.num_layers,
            layer_kinds=theirs.layer_kinds[:cfg.num_layers])
        if theirs != cfg:
            raise SystemExit(
                f"benchmark: {config['program_config']} differs from the "
                f"published configuration: {theirs} != {cfg}")
    return cfg


def init_weights(key, cfg, dtype):
    """The program's own draw (matrices and taps uniform in +-1/sqrt(fan
    in), norm weights ones, the routers' expert bias uniform in +-0.05
    where the published initialiser writes zeros, so that "selected on s +
    b, weighted by s" shows), with the routed experts of a layer made a
    sixteenth their own and the rest one shared draw, as
    ``glm4_moe_lite.init_weights`` makes them and for its reason
    (``EXPERT_OWN_SHARE``): renormalised sigmoid scores give the fourth
    pick a quarter of the routed sum whatever a router gain, there is no
    shared expert beside them here, and a rounding that swaps the fourth
    pick for the fifth would replace that quarter of a layer's whole MLP
    by an unrelated function.  The CPU tests hold single picks with
    unrelated experts (tests/engine/test_lfm2_moe.py)."""
    return glm4_moe_lite.init_weights(key, cfg, dtype)


def param_specs(shapes):
    raise SystemExit("benchmark: the state cache of a model with short "
                     "convolutions has no tensor-parallel layout; "
                     "tensor_parallel_size is 1")


# -------------------------------------------------------------- reference
#: experts upcast to float32 at a time: bounds the transient to
#: 8 x (2048 x 3584 + 1792 x 2048) x 4 B = 0.35 GB of weights and
#: 8 x 4616 x (3584 + 1792 + 2048) x 4 B = 1.1 GB of activations beside a
#: full chip
EXPERT_CHUNK = 8
#: query rows of the dense masked softmax computed at a time: 32 heads x
#: 512 x 4616 scores x 4 B = 0.3 GB, where the whole square is 2.7 GB
QUERY_BLOCK = 512


def _f32(a):
    return a.astype(jnp.float32)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, positions, theta):
    """x [T, H, D]; rotate the two halves of D."""
    half = x.shape[-1] // 2
    inv_freq = 1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    # stack + reshape, not concatenate on the last axis: see
    # benchmark/archs/qwen3_moe.py _rope
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                     axis=-2).reshape(x.shape)


def _swiglu(h, gate_up, down):
    gate, up = jnp.split(h @ _f32(gate_up), 2, axis=-1)
    return (jax.nn.silu(gate) * up) @ _f32(down)


def _sparse_mlp(layer, h, top_k, renorm, scale):
    """The routed experts' sum for normed rows h: every expert computed
    for every token, weighted by the router (zero where it did not pick
    the expert)."""
    t = h.shape[0]
    scores = jax.nn.sigmoid(h @ _f32(layer["router"]["w"]))
    _, top_i = jax.lax.top_k(scores + _f32(layer["router"]["e_bias"]), top_k)
    top_w = jnp.take_along_axis(scores, top_i, axis=-1)
    if renorm:
        top_w = top_w / jnp.sum(top_w, -1, keepdims=True)
    combine = jnp.zeros_like(scores).at[jnp.arange(t)[:, None], top_i].set(
        top_w * scale)
    y = jnp.zeros_like(h)
    for e0 in range(0, scores.shape[-1], EXPERT_CHUNK):
        sl = slice(e0, e0 + EXPERT_CHUNK)
        gu = jnp.einsum("th,ehf->etf", h, _f32(layer["experts"]["gate_up"][sl]))
        gate, up = jnp.split(gu, 2, axis=-1)
        out = jnp.einsum("etf,efh->eth", jax.nn.silu(gate) * up,
                         _f32(layer["experts"]["down"][sl]))
        y = y + jnp.einsum("eth,te->th", out, combine[:, sl])
    return y


def _shortconv(layer, h):
    """The gated short convolution of normed rows h [T, hidden], one
    sequence from its first token: the taps as a sum of shifted
    products."""
    sc = layer["shortconv"]
    t = h.shape[0]
    gate_b, gate_c, u = jnp.split(h @ _f32(sc["in_proj"]["w"]), 3, axis=-1)
    v = gate_b * u
    w = _f32(sc["conv"]["w"])                     # [K, hidden], w[K-1] on t
    kk = w.shape[0]
    padded = jnp.pad(v, ((kk - 1, 0), (0, 0)))    # zeros before token 0
    conv = sum(padded[j:j + t] * w[j] for j in range(kk))
    return (gate_c * conv) @ _f32(sc["out_proj"]["w"])


def _attention(layer, h, theta, eps):
    """Grouped-query attention of normed rows h [T, hidden], one sequence:
    a dense masked softmax, ``QUERY_BLOCK`` query rows at a time."""
    t = h.shape[0]
    d = layer["q_norm"]["w"].shape[0]
    pos = jnp.arange(t)
    q = (h @ _f32(layer["q_proj"]["w"])).reshape(t, -1, d)
    k = (h @ _f32(layer["k_proj"]["w"])).reshape(t, -1, d)
    v = (h @ _f32(layer["v_proj"]["w"])).reshape(t, -1, d)
    q = _rope(_rms(q, _f32(layer["q_norm"]["w"]), eps), pos, theta)
    k = _rope(_rms(k, _f32(layer["k_norm"]["w"]), eps), pos, theta)
    group = q.shape[1] // k.shape[1]
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    outs = []
    for t0 in range(0, t, QUERY_BLOCK):
        rows = pos[t0:t0 + QUERY_BLOCK]
        s = jnp.einsum("thd,shd->hts", q[t0:t0 + QUERY_BLOCK], k) \
            / jnp.sqrt(float(d))
        s = jnp.where(rows[None, :, None] >= pos[None, None, :], s, -jnp.inf)
        outs.append(jnp.einsum("hts,shd->thd", jax.nn.softmax(s, -1), v))
    o = jnp.concatenate(outs, axis=0)
    return o.reshape(t, -1) @ _f32(layer["o_proj"]["w"])


@functools.partial(jax.jit, static_argnames=("hp",))
def _layer(layer, x, hp):
    """One block on x [T, hidden] float32; ``hp`` = (eps, theta, top_k,
    renormalise, scaling factor).  What a layer is, its weights say."""
    eps, theta, top_k, renorm, scale = hp
    h = _rms(x, _f32(layer["input_norm"]["w"]), eps)
    if "shortconv" in layer:
        x = x + _shortconv(layer, h)
    else:
        x = x + _attention(layer, h, theta, eps)
    h = _rms(x, _f32(layer["post_norm"]["w"]), eps)
    if "experts" in layer:
        return x + _sparse_mlp(layer, h, top_k, renorm, scale)
    return x + _swiglu(h, layer["gate_up"]["w"], layer["down"]["w"])


@jax.jit
def _head(norm_w, embed, x, eps):
    h = _rms(x, _f32(norm_w), eps)
    return jax.nn.log_softmax(h @ _f32(embed).T, -1)


def reference_logprobs(params, config: dict, token_ids: list[int],
                       last_n: int, tp: int = 1) -> jax.Array:
    """Log-softmax over the vocabulary after the last ``last_n`` positions
    of ``token_ids``: [last_n, vocab] float32.  ``params`` is the served
    weight tree (upcast here, layer by layer, the experts in blocks);
    ``config`` holds the keys of the configuration file."""
    if tp != 1:
        raise SystemExit("benchmark: the LFM2 reference has no "
                         "tensor-parallel weight layout")
    eps = config["norm_eps"]
    hp = (eps, float(config["rope_theta"]), config["num_experts_per_tok"],
          bool(config["norm_topk_prob"]),
          float(config["routed_scaling_factor"]))
    ids = jnp.asarray(token_ids, jnp.int32)
    with jax.default_matmul_precision("highest"):
        x = params["embed"]["w"][ids].astype(jnp.float32)
        for layer in params["layers"]:
            x = _layer(layer, x, hp)
        return _head(params["final_norm"]["w"], params["embed"]["w"],
                     x[-last_n:], eps)


# ----------------------------------------------------------------- counts
def model_costs(config: dict, dtype_bytes: int = 2) -> dict:
    """Operations and bytes the decoder's algorithm needs, from the sizes
    of the configuration file.  A query widened to its pool's row (the
    program's way to 64-wide heads) is the program's cost and is in none
    of these."""
    h, heads = config["hidden_size"], config["num_attention_heads"]
    d = h // heads
    q_dim, kv_dim = heads * d, config["num_key_value_heads"] * d
    mix = _layer_mix(config)
    experts, top_k = config["num_experts"], config["num_experts_per_tok"]
    kk = config["conv_L_cache"]
    attn_params = h * q_dim + 2 * h * kv_dim + q_dim * h
    conv_params = 3 * h * h + h * h          # in_proj and out_proj
    expert_params = 3 * h * config["moe_intermediate_size"]
    dense_params = 3 * h * config["intermediate_size"]
    router_params = h * experts
    # a token through one convolution: B * u, K taps (a product and a sum
    # each) and the gate C
    conv_flops = (2.0 * kk + 2.0) * h
    return {
        "mix": mix, "experts": experts, "top_k": top_k, "q_dim": q_dim,
        "conv_flops_per_token": conv_flops,
        "flops_per_token": 2.0 * (
            mix["attention"] * attn_params + mix["conv"] * conv_params
            + mix["dense"] * dense_params
            + mix["sparse"] * (router_params + top_k * expert_params))
        + mix["conv"] * conv_flops,
        "moe_flops_per_token": 2.0 * (router_params + top_k * expert_params),
        # a pair a query head: the score over D numbers and the value over
        # D, a product and a sum each
        "attn_flops_per_pair": 4.0 * q_dim * mix["attention"],
        "head_flops_per_row": 2.0 * h * config["vocab_size"],
        "fixed_weight_bytes": dtype_bytes * (
            mix["attention"] * attn_params
            + mix["conv"] * (conv_params + kk * h)
            + mix["dense"] * dense_params + mix["sparse"] * router_params
            + h * config["vocab_size"]),
        "router_bytes": dtype_bytes * router_params,
        "expert_bytes": dtype_bytes * expert_params,
        "tap_bytes": dtype_bytes * kk * h,
        # K and V of every kv head, the attention layers only: 64-wide
        # heads lie two to a row, so no lane of a page is padding
        "kv_bytes_per_pos": dtype_bytes * 2 * kv_dim * mix["attention"],
        # one sequence's state in one convolution: its last K - 1 rows of
        # B * u, float32
        "state_bytes": 4 * (kk - 1) * h,
    }


def _touched(costs: dict, tokens: float) -> float:
    """Experts that ``tokens`` tokens are expected to route to."""
    return costs["experts"] * (
        1.0 - (1.0 - costs["top_k"] / costs["experts"]) ** tokens)


def _state_rows(step: dict) -> int:
    """Sequences whose state the step read and wrote (the flight record's
    ``state_rows``; a record without it has one a row)."""
    return step.get("state_rows", step["prefills"] + step["decodes"])


def step_cost(step: dict, config: dict, mean_prefill_ctx: float,
              mean_decode_ctx: float, dtype_bytes: int = 2
              ) -> tuple[float, float] | None:
    """One whole step (``prefill_tokens``, ``decodes``, ``prefills``), as
    ``qwen3_moe.step_cost`` counts it: every weight it touches read once
    (of a sparse layer's experts as many as its tokens are expected to
    route to), attention's pairs and cache reads in the attention layers
    only, and in each convolution every touched sequence's state read and
    written once."""
    costs = model_costs(config, dtype_bytes)
    mix = costs["mix"]
    tokens = step["prefill_tokens"] + step["decodes"]
    if tokens <= 0:
        return None
    pairs = (step["prefill_tokens"] * mean_prefill_ctx
             + step["decodes"] * mean_decode_ctx)
    flops = (costs["flops_per_token"] * tokens
             + costs["attn_flops_per_pair"] * pairs
             + costs["head_flops_per_row"] * (step["prefills"]
                                              + step["decodes"]))
    kv_read = (step["decodes"] * mean_decode_ctx
               + step["prefills"] * 2 * mean_prefill_ctx)
    nbytes = (costs["fixed_weight_bytes"]
              + mix["sparse"] * _touched(costs, tokens)
              * costs["expert_bytes"]
              + costs["kv_bytes_per_pos"] * (kv_read + tokens)
              + mix["conv"] * 2.0 * _state_rows(step) * costs["state_bytes"])
    return flops, nbytes


def attn_cost(step: dict, config: dict, dtype_bytes: int = 2
              ) -> tuple[float, float]:
    """The attention of one step, as ``qwen3_moe.attn_cost`` counts it,
    over the attention layers only, at heads of 64: ``4 x 64 x ctx``
    operations a query head, 2 048 B a cached token a layer.  Bytes: every
    decode row reads its context once, a chunk reads what it attends
    once, and queries come in and outputs go out at the head's width."""
    costs = model_costs(config, dtype_bytes)
    tokens = step["prefill_tokens"] + step["decodes"]
    kv_read = step["decode_ctx"]
    if step["prefill_tokens"] > 0:
        kv_read += (step["prefills"] * step["prefill_ctx"]
                    / step["prefill_tokens"] + step["prefill_tokens"] / 2.0)
    flops = costs["attn_flops_per_pair"] * (step["prefill_ctx"]
                                            + step["decode_ctx"])
    nbytes = (costs["kv_bytes_per_pos"] * kv_read
              + 2.0 * dtype_bytes * costs["mix"]["attention"] * tokens
              * costs["q_dim"])
    return flops, nbytes


def moe_cost(step: dict, config: dict, dtype_bytes: int = 2
             ) -> tuple[float, float]:
    """The sparse MLPs of one step (what runs under ``omni.moe``; the two
    leading dense MLPs are ``omni.mlp``'s): the router and the picked
    experts per token; the router's weights and as many experts as the
    step's tokens are expected to touch, read once a layer, and the
    activations in and out."""
    costs = model_costs(config, dtype_bytes)
    sparse = costs["mix"]["sparse"]
    tokens = step["prefill_tokens"] + step["decodes"]
    flops = sparse * tokens * costs["moe_flops_per_token"]
    nbytes = sparse * (
        _touched(costs, tokens) * costs["expert_bytes"]
        + costs["router_bytes"]
        + 2.0 * dtype_bytes * tokens * config["hidden_size"])
    return flops, nbytes


def shortconv_cost(step: dict, config: dict, dtype_bytes: int = 2
                   ) -> tuple[float, float]:
    """What runs under ``omni.shortconv`` in one step (the projections are
    ``omni.proj``'s, the slots' gathers and scatters ``omni.conv_state``'s):
    per token and channel the product ``B * u``, three taps and the gate
    ``C``.  Bytes: the taps once a layer, each token's row in (B, C and u)
    and out, and every touched sequence's carried rows read and its new
    ones written once a layer."""
    costs = model_costs(config, dtype_bytes)
    conv = costs["mix"]["conv"]
    tokens = step["prefill_tokens"] + step["decodes"]
    flops = conv * tokens * costs["conv_flops_per_token"]
    nbytes = conv * (
        costs["tap_bytes"]
        + 4.0 * dtype_bytes * tokens * config["hidden_size"]
        + 2.0 * _state_rows(step) * costs["state_bytes"])
    return flops, nbytes
