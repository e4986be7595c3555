"""The GLM-4.7-Flash decoder (``model_type`` ``glm4_moe_lite``), as a
configuration file names it: ``"arch": "benchmark.archs.glm4_moe_lite"``.
It keeps the contract of ``benchmark/archs/qwen3_moe.py``
(``program_config``, ``init_weights``, ``param_specs``,
``reference_logprobs``, ``step_cost``, ``attn_cost``, ``moe_cost``) and adds
``mla_up_cost`` for the roofline of the products with ``kv_b``.

The model (residual block ``x += attn(norm1(x)); x += mlp(norm2(x))``; H
heads; every ``norm`` is ``x * rsqrt(mean(x^2) + eps) * w``):

*Latent attention*, every layer.  ``c_q = norm(x W_qa)`` (q_lora_rank);
``[q_nope | q_rope]_h = c_q W_qb`` (a head: qk_nope | qk_rope);
``[c_kv | k_rope] = x W_kva`` (kv_lora_rank | qk_rope); ``c_kv =
norm(c_kv)``; rotary (rotate-half, theta) over all of ``q_rope_h`` and of
the one ``k_rope`` every head shares.  ``[k_nope | v]_h = c_kv W_kvb`` (a
head: qk_nope | v_head_dim).  ``s_h(t, u) = (q_nope_h(t) . k_nope_h(u) +
q_rope_h(t) . k_rope(u)) / sqrt(qk_nope + qk_rope)``, causal softmax,
``o_h = sum_u p v_h(u)``, output ``concat_h(o_h) W_o``.

*MLP.*  The first ``first_k_dense_replace`` layers: ``down(silu(gate x) *
up x)`` at ``intermediate_size``.  The others: ``s = sigmoid(x W_r)`` in
float32 over the experts; the ``num_experts_per_tok`` largest of ``s + b``
(``e_score_correction_bias``); weights ``s_e / sum_picks s`` times
``routed_scaling_factor``; ``y = sum_e w_e expert_e(x) + shared(x)``, the
shared expert added whole.

*Head.*  Final norm, untied ``lm_head``.

The reference is straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``, in the EXPANDED form only:
per-head keys and values from ``W_kvb`` for every position, attention as a
dense masked softmax, every expert computed for every token and masked by
the router's weights, no cache, no kernel, no batching, the experts in
blocks so that 2.6k tokens fit beside a full chip.  It shares only the
names of the weight tree with the program (whose modules are imported
inside the builder functions alone), which serves the ABSORBED form over a
latent cache; the sizes come from the configuration file's keys.

Departures from the published model, each under the file's ``assumed``:
the one multi-token-prediction module (``num_nextn_predict_layers``) is
not served; rotary pairs the rotary dims as two halves (``config.json``
does not say; the interleaved pairing is a fixed permutation of columns of
``W_qb`` and ``W_kva``).
"""

from __future__ import annotations

import dataclasses
import functools
import importlib

import jax
import jax.numpy as jnp


# ---------------------------------------------------------------- builder
def program_config(config: dict):
    """The published keys of a configuration file -> the program's
    ``TransformerConfig``.  Where the file names the program's own constant
    for this model (``program_config``), the two must agree on everything
    but depth: a width that drifted in the program is an error here."""
    from vllm_omni_tpu.models.common.transformer import TransformerConfig

    for key, want in (("topk_method", "noaux_tc"), ("n_group", 1),
                      ("topk_group", 1), ("hidden_act", "silu"),
                      ("attention_bias", False), ("rope_scaling", None),
                      ("partial_rotary_factor", 1)):
        if config[key] != want:
            raise SystemExit(f"benchmark: {key} {config[key]!r} is not "
                             f"built (only {want!r})")
    cfg = TransformerConfig(
        vocab_size=config["vocab_size"],
        hidden_size=config["hidden_size"],
        num_layers=config["num_hidden_layers"],
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        head_dim=config["qk_nope_head_dim"] + config["qk_rope_head_dim"],
        intermediate_size=config["intermediate_size"],
        rope_theta=float(config["rope_theta"]),
        rms_eps=config["rms_norm_eps"],
        tie_word_embeddings=bool(config["tie_word_embeddings"]),
        moe=True,
        num_experts=config["n_routed_experts"],
        num_experts_per_tok=config["num_experts_per_tok"],
        moe_intermediate_size=config["moe_intermediate_size"],
        moe_renormalize=bool(config["norm_topk_prob"]),
        shared_expert_size=(config["n_shared_experts"]
                            * config["moe_intermediate_size"]),
        shared_expert_gate=False,
        first_k_dense_replace=config["first_k_dense_replace"],
        router_scoring="sigmoid",
        router_bias=True,
        routed_scaling_factor=float(config["routed_scaling_factor"]),
        q_lora_rank=config["q_lora_rank"],
        kv_lora_rank=config["kv_lora_rank"],
        qk_nope_head_dim=config["qk_nope_head_dim"],
        qk_rope_head_dim=config["qk_rope_head_dim"],
        v_head_dim=config["v_head_dim"],
    )
    if _layer_mix(config) != (cfg.first_k_dense_replace,
                              cfg.num_layers - cfg.first_k_dense_replace):
        raise SystemExit(f"benchmark: stage_layers {config['stage_layers']} "
                         f"are not the {cfg.num_layers} layers served, "
                         f"{cfg.first_k_dense_replace} of them dense")
    if config.get("program_config"):
        module, _, name = config["program_config"].partition(":")
        theirs = dataclasses.replace(
            getattr(importlib.import_module(module), name),
            num_layers=cfg.num_layers)
        if theirs != cfg:
            raise SystemExit(
                f"benchmark: {config['program_config']} differs from the "
                f"published configuration: {theirs} != {cfg}")
    return cfg


#: The share of a routed expert's matrices that is its own; the rest is one
#: matrix all experts of the layer share (``W_e = sqrt(1 - a^2) W_0 + a
#: W_e'``, every entry's variance as drawn).  With 64 unrelated random
#: experts a rounding that swaps a token's fourth and fifth pick (sigmoid
#: scores 0.01 apart, renormalised to a quarter of the routed sum each,
#: times 1.8) replaces nearly half of that sum by an unrelated function,
#: and the changed stream swaps picks in the layers after it: one token in
#: ten to twenty then reads 0.1-0.3 from the float32 reference where the
#: others read 0.01 (PERF.md, Findings, PR 32: the chip's first check read
#: 0.129 on one token of eight; a CPU simulation of the served roundings
#: at these widths 1.2-1.6% of tokens past 0.15).  Neither a float32
#: residual stream nor a larger embedding halves that, and a router gain,
#: ``qwen3_next.ROUTER_GAIN``'s cure for a softmax router, cannot make a
#: sigmoid router's marginal pick small: renormalised sigmoid scores of the
#: top four are equal whatever the gain, and a gain saturates them so that
#: the bias alone selects (at 4, 3 of 64 experts are never picked in 4096
#: tokens).  A trained layer's experts are not unrelated functions either.
#: The chip's check over 2560 + 8 tokens, largest of eight tokens, by
#: share: 1 (unrelated) 0.015 and 0.129 in two seeds; 1/4: 0.160 (not
#: correct), 0.043, 0.022, 0.021; 1/8: 0.038, 0.036, 0.013, 0.020, 0.010,
#: 0.017; **1/16: 0.015, 0.018, 0.022, 0.023, 0.019, 0.013**, which is the
#: reading of a token whose picks all agree (my chip runs, PR 32).  What
#: the check then no longer sees is ONE wrong pick; a wrong weight, a
#: missing scaling factor or renormalisation, the shared expert or a
#: selection that is wrong in every token still shows at full size through
#: the shared part, and the CPU tests hold single picks to 2e-5 with
#: unrelated experts (tests/engine/test_glm4_moe_lite.py, tests/ops/
#: test_moe.py).  A power of two: the own part's product is exact.
EXPERT_OWN_SHARE = 0.0625


def init_weights(key, cfg, dtype):
    """The program's own draw (matrices uniform in +-1/sqrt(fan_in), norm
    weights ones, the routers' correction bias uniform in +-0.05 where the
    published initialiser writes zeros: a third of the sigmoid scores' own
    spread, so that the selection on ``s + b`` differs from one on ``s``
    in most tokens while every expert is still picked about as often as
    the others), with what a decisive check needs and no more: the routed
    experts of a layer made ``EXPERT_OWN_SHARE`` their own."""
    from vllm_omni_tpu.models.common import transformer as tfm

    params = tfm.init_params(key, cfg=cfg, dtype=dtype)
    a = EXPERT_OWN_SHARE

    def related(w):
        """[E, ...]: expert 0's draw stands for the shared part.  One
        expert at a time (``lax.map``), so that the step's temporaries are
        an expert's and not the float32 image of every layer's experts at
        once (8 GB beside 10 GB of weights, compiled for a v5e)."""
        shared = (1.0 - a * a) ** 0.5 * w[0].astype(jnp.float32)
        return jax.lax.map(
            lambda own: (shared + a * own.astype(jnp.float32)).astype(
                w.dtype), w)

    for layer in params["layers"]:
        if "experts" in layer:
            layer["experts"] = {k: related(v)
                                for k, v in layer["experts"].items()}
    return params


def param_specs(shapes):
    raise SystemExit("benchmark: the latent cache has no tensor-parallel "
                     "layout; tensor_parallel_size is 1")


# -------------------------------------------------------------- reference
#: experts upcast to float32 at a time: bounds the transient to
#: 8 x (2048 x 3072 + 1536 x 2048) x 4 B = 0.3 GB of weights and
#: 8 x 2568 x (3072 + 1536 + 2048) x 4 B = 0.55 GB of activations beside
#: a full chip
EXPERT_CHUNK = 8


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
    """The routed experts' and the shared expert's sum for normed rows h:
    every expert computed for every token, masked by the router's
    weights."""
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
    return y + _swiglu(h, layer["shared_expert"]["gate_up"]["w"],
                       layer["shared_expert"]["down"]["w"])


@functools.partial(jax.jit, static_argnames=("hp",))
def _layer(layer, x, hp):
    """One block on x [T, hidden] float32, attention in the expanded form;
    ``hp`` = (qk_nope, qk_rope, v_head_dim, kv_lora_rank, eps, theta,
    top_k, renormalise, scaling factor)."""
    dn, dr, dv, r, eps, theta, top_k, renorm, scale = hp
    m = layer["mla"]
    t = x.shape[0]
    pos = jnp.arange(t)
    h = _rms(x, _f32(layer["input_norm"]["w"]), eps)
    c_q = _rms(h @ _f32(m["q_a"]["w"]), _f32(m["q_a_norm"]["w"]), eps)
    q = (c_q @ _f32(m["q_b"]["w"])).reshape(t, -1, dn + dr)
    kv = h @ _f32(m["kv_a"]["w"])
    c_kv = _rms(kv[:, :r], _f32(m["kv_a_norm"]["w"]), eps)
    k_rope = _rope(kv[:, None, r:], pos, theta)                 # [T, 1, dr]
    q_rope = _rope(q[..., dn:], pos, theta)
    # every position's per-head key and value, from its latent row
    kv_h = (c_kv @ _f32(m["kv_b"]["w"])).reshape(t, -1, dn + dv)
    s = (jnp.einsum("thd,shd->hts", q[..., :dn], kv_h[..., :dn])
         + jnp.einsum("thd,sd->hts", q_rope, k_rope[:, 0])
         ) / jnp.sqrt(float(dn + dr))
    s = jnp.where(pos[None, :, None] >= pos[None, None, :], s, -jnp.inf)
    o = jnp.einsum("hts,shd->thd", jax.nn.softmax(s, -1), kv_h[..., dn:])
    x = x + o.reshape(t, -1) @ _f32(layer["o_proj"]["w"])
    h = _rms(x, _f32(layer["post_norm"]["w"]), eps)
    if "experts" in layer:
        return x + _sparse_mlp(layer, h, top_k, renorm, scale)
    return x + _swiglu(h, layer["gate_up"]["w"], layer["down"]["w"])


@jax.jit
def _head(params, x, eps):
    h = _rms(x, _f32(params["final_norm"]["w"]), eps)
    return jax.nn.log_softmax(h @ _f32(params["lm_head"]["w"]), -1)


def reference_logprobs(params, config: dict, token_ids: list[int],
                       last_n: int, tp: int = 1) -> jax.Array:
    """Log-softmax over the vocabulary after the last ``last_n`` positions
    of ``token_ids``: [last_n, vocab] float32.  ``params`` is the served
    weight tree (upcast here, layer by layer, the experts in blocks);
    ``config`` holds the keys of the configuration file."""
    if tp != 1:
        raise SystemExit("benchmark: the GLM-4.7-Flash reference has no "
                         "tensor-parallel weight layout")
    eps = config["rms_norm_eps"]
    hp = (config["qk_nope_head_dim"], config["qk_rope_head_dim"],
          config["v_head_dim"], config["kv_lora_rank"], eps,
          float(config["rope_theta"]), config["num_experts_per_tok"],
          bool(config["norm_topk_prob"]),
          float(config["routed_scaling_factor"]))
    ids = jnp.asarray(token_ids, jnp.int32)
    with jax.default_matmul_precision("highest"):
        x = params["embed"]["w"][ids].astype(jnp.float32)
        for layer in params["layers"]:
            x = _layer(layer, x, hp)
        return _head({k: params[k] for k in ("final_norm", "lm_head")},
                     x[-last_n:], eps)


# ----------------------------------------------------------------- counts
def _layer_mix(config: dict) -> tuple[float, float]:
    """(dense, sparse) layers among the file's ``num_hidden_layers``, in
    the mix its ``stage_layers`` state (the pipeline stage served here:
    the leading dense layer and seven sparse ones).  At the file's own
    depth these are its layers; the counts of a model twice as deep are
    those of two such stages, so that every count of the layers is
    linear in depth, as the harness's contract has it."""
    stage = config["stage_layers"]
    share = config["num_hidden_layers"] / (stage["dense"] + stage["sparse"])
    return stage["dense"] * share, stage["sparse"] * share


def model_costs(config: dict, dtype_bytes: int = 2) -> dict:
    """Operations and bytes the decoder's algorithm needs, from the sizes
    of the configuration file.  A padded lane or a padded head is the
    program's cost and is in none of these."""
    h, heads = config["hidden_size"], config["num_attention_heads"]
    dn, dr = config["qk_nope_head_dim"], config["qk_rope_head_dim"]
    dv, r, qr = (config["v_head_dim"], config["kv_lora_rank"],
                 config["q_lora_rank"])
    layers = config["num_hidden_layers"]
    dense, sparse = _layer_mix(config)
    experts, top_k = config["n_routed_experts"], config["num_experts_per_tok"]
    # the projections of a layer without kv_b: q_a, q_b, kv_a, o_proj
    proj_params = h * qr + qr * heads * (dn + dr) + h * (r + dr) \
        + heads * dv * h
    kvb_params = r * heads * (dn + dv)
    expert_params = 3 * h * config["moe_intermediate_size"]
    shared_params = config["n_shared_experts"] * expert_params
    dense_params = 3 * h * config["intermediate_size"]
    sparse_fixed = h * experts + shared_params  # router and shared expert
    return {
        "layers": layers, "dense": dense, "sparse": sparse,
        "experts": experts, "top_k": top_k, "heads": heads,
        "row": r + dr, "r": r, "dn": dn, "dr": dr, "dv": dv,
        "kvb_params": kvb_params,
        "flops_per_token": 2.0 * (
            layers * (proj_params + kvb_params) + dense * dense_params
            + sparse * (sparse_fixed + top_k * expert_params)),
        "moe_flops_per_token": 2.0 * (sparse_fixed + top_k * expert_params),
        # a decode row's pair, absorbed: the score over the row's r + dr
        # numbers and the value over its first r, every head
        "decode_flops_per_pair": 2.0 * heads * (2 * r + dr) * layers,
        # a prefill token's pair, expanded: per-head key dn + dr, value dv
        "prefill_flops_per_pair": 2.0 * heads * (dn + dr + dv) * layers,
        "head_flops_per_row": 2.0 * h * config["vocab_size"],
        "fixed_weight_bytes": dtype_bytes * (
            layers * (proj_params + kvb_params) + dense * dense_params
            + sparse * sparse_fixed + h * config["vocab_size"]),
        "sparse_fixed_bytes": dtype_bytes * sparse_fixed,
        "expert_bytes": dtype_bytes * expert_params,
        # the latent row: all a cached position holds, all layers
        "kv_bytes_per_pos": dtype_bytes * layers * (r + dr),
    }


def _touched(costs: dict, tokens: float) -> float:
    """Experts that ``tokens`` tokens are expected to route to."""
    return costs["experts"] * (
        1.0 - (1.0 - costs["top_k"] / costs["experts"]) ** tokens)


def step_cost(step: dict, config: dict, mean_prefill_ctx: float,
              mean_decode_ctx: float, dtype_bytes: int = 2
              ) -> tuple[float, float] | None:
    """One whole step (``prefill_tokens``, ``decodes``, ``prefills``), as
    ``qwen3_moe.step_cost`` counts it: every weight it touches read once
    (of the experts as many as its tokens are expected to route to),
    attention's pairs at the cheaper published form of each kind of row
    (absorbed for a decode row, expanded for a prefill token) and the
    latent rows it attends read once a decode row and once a chunk."""
    costs = model_costs(config, dtype_bytes)
    tokens = step["prefill_tokens"] + step["decodes"]
    if tokens <= 0:
        return None
    flops = (costs["flops_per_token"] * tokens
             + costs["prefill_flops_per_pair"]
             * step["prefill_tokens"] * mean_prefill_ctx
             + costs["decode_flops_per_pair"]
             * step["decodes"] * mean_decode_ctx
             + costs["head_flops_per_row"] * (step["prefills"]
                                              + step["decodes"]))
    kv_read = (step["decodes"] * mean_decode_ctx
               + step["prefills"] * 2 * mean_prefill_ctx)
    nbytes = (costs["fixed_weight_bytes"]
              + costs["sparse"] * _touched(costs, tokens)
              * costs["expert_bytes"]
              + costs["kv_bytes_per_pos"] * (kv_read + tokens))
    return flops, nbytes


def attn_cost(step: dict, config: dict, dtype_bytes: int = 2
              ) -> tuple[float, float]:
    """The attention of one step (a record with ``prefill_ctx`` /
    ``decode_ctx``, the attended pairs of its prefill tokens and of its
    decode rows), each kind of row at its cheaper published form whatever
    the program runs: a decode row absorbed (2 x H x ctx x (2r + dr)
    operations over ctx latent rows of r + dr numbers), a prefill token
    expanded and causal (2 x H x (dn + dr + dv) a pair).  Bytes: every
    decode row reads its context's latent rows once, a chunk reads those
    it attends once, and queries come in and outputs go out at the form's
    widths; the row write is ``omni.kv_write``'s and the products with
    ``kv_b`` are ``mla_up_cost``'s."""
    costs = model_costs(config, dtype_bytes)
    heads, layers = costs["heads"], costs["layers"]
    kv_read = step["decode_ctx"]
    if step["prefill_tokens"] > 0:
        kv_read += (step["prefills"] * step["prefill_ctx"]
                    / step["prefill_tokens"] + step["prefill_tokens"] / 2.0)
    flops = (costs["prefill_flops_per_pair"] * step["prefill_ctx"]
             + costs["decode_flops_per_pair"] * step["decode_ctx"])
    q_out = dtype_bytes * layers * heads * (
        step["decodes"] * (costs["row"] + costs["r"])
        + step["prefill_tokens"] * (costs["dn"] + costs["dr"] + costs["dv"]))
    return flops, costs["kv_bytes_per_pos"] * kv_read + q_out


def mla_up_cost(step: dict, config: dict, dtype_bytes: int = 2
                ) -> tuple[float, float]:
    """What runs under ``omni.mla_up`` in one step: every product with
    ``kv_b``, each token's once.  Expanding a token's latent row to its
    per-head keys and values, or absorbing its query and un-absorbing its
    output, is the same 2 x r x H x (dn + dv) operations.  Bytes: ``kv_b``
    once a layer, and the rows in and out at the cheaper form of each
    kind: a prefill token's latent row in and its per-head [k_nope | v]
    out; a decode row's q_nope in and latent query out, latent output in
    and per-head output out."""
    costs = model_costs(config, dtype_bytes)
    heads, r, dn, dv = costs["heads"], costs["r"], costs["dn"], costs["dv"]
    tokens = step["prefill_tokens"] + step["decodes"]
    flops = 2.0 * costs["layers"] * tokens * costs["kvb_params"]
    nbytes = dtype_bytes * costs["layers"] * (
        costs["kvb_params"]
        + step["prefill_tokens"] * (r + heads * (dn + dv))
        + step["decodes"] * heads * (dn + 2 * r + dv))
    return flops, nbytes


def moe_cost(step: dict, config: dict, dtype_bytes: int = 2
             ) -> tuple[float, float]:
    """The sparse MLPs of one step (what runs under ``omni.moe``; the
    leading dense layer is ``omni.mlp``): the router, the picked experts
    and the shared expert per token; the router's and the shared expert's
    weights and as many experts as the step's tokens are expected to
    touch, read once a layer, and the activations in and out."""
    costs = model_costs(config, dtype_bytes)
    tokens = step["prefill_tokens"] + step["decodes"]
    flops = costs["sparse"] * tokens * costs["moe_flops_per_token"]
    nbytes = costs["sparse"] * (
        _touched(costs, tokens) * costs["expert_bytes"]
        + costs["sparse_fixed_bytes"]
        + 2.0 * dtype_bytes * tokens * config["hidden_size"])
    return flops, nbytes
