"""The Qwen3-Next decoder (Qwen3-Next-80B-A3B-Instruct), as a configuration
file names it: ``"arch": "benchmark.archs.qwen3_next"``.  It keeps the
contract of ``benchmark/archs/qwen3_moe.py`` (``program_config``,
``init_weights``, ``param_specs``, ``reference_logprobs``, ``step_cost``,
``attn_cost``, ``moe_cost``) and adds ``gdn_cost`` for the roofline of the
Gated DeltaNet layers.

The model (residual block ``x += mixer(norm1(x)); x += moe(norm2(x))``;
layer ``i`` is softmax attention iff ``(i + 1) % full_attention_interval ==
0``; every ``norm`` is ``x * rsqrt(mean(x^2) + eps) * (1 + w)``):

*Gated DeltaNet* (``linear_num_key_heads`` Hk, ``linear_num_value_heads``
Hv, head widths Dk and Dv, ``linear_conv_kernel_dim`` 4).  Projections
hidden -> q Hk Dk, k Hk Dk, v Hv Dv, z Hv Dv, b Hv, a Hv.  ``(q, k, v) =
silu(causal_depthwise_conv4(concat(q, k, v)))``, no bias.  ``beta =
sigmoid(b)``, ``g = -exp(A_log) * softplus(a + dt_bias)``.  Each key head
serves Hv / Hk value heads.  q and k are L2-normalised over the head
(eps 1e-6), q scaled by ``Dk^-0.5``.  A head, a token t, a state S of
[Dk, Dv] in float32: ``S = exp(g_t) S``; ``u = beta_t (v_t - S^T k_t)``;
``S = S + k_t u^T``; ``o_t = S^T q_t``.  Output, a head at a time:
``w * o * rsqrt(mean(o^2) + eps) * silu(z)`` (plain weight, the gate after
the norm), then the out projection.

*Gated attention*.  ``q_proj`` gives every head ``[q | gate]``; q and k
take the ``(1 + w)`` RMSNorm over the head, then rotary (rotate-half) on
the first ``head_dim * partial_rotary_factor`` dims only.  Causal softmax
attention at ``head_dim^-0.5``; ``o = attn * sigmoid(gate)``; ``o_proj``.

*Sparse MLP*, every layer.  ``p = softmax(x W_r)`` over the router's whole
width in float32; the ``num_experts_per_tok`` largest, renormalised over
those; ``y = sum_e p_e down_e(silu(gate_e x) * up_e x)`` over the picks
that are held here, ``+ sigmoid(x w_sg) * shared(x)``.

The reference is straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``: the delta rule token by token
(``lax.scan`` over the recurrence, no chunking), the convolution as a sum
of four shifted products, attention as a dense masked softmax, every held
expert computed for every token and masked by the router's weights, no
kernel, no cache, no batching.  It shares only the names of the weight
tree with the program (whose modules are imported inside the builder
functions alone); the sizes come from the configuration file's keys.

The chip's share: the file's ``num_experts`` counts the experts HELD here
(ids ``expert_offset .. expert_offset + num_experts``), its
``published.num_experts`` is the router's width.  A pick that lives on
another chip adds nothing, in the program and here alike, and that partial
result goes on to the next layer (model-configs guide, section 4).

Departures from the published model: it has one multi-token-prediction
module ("MTP 1"); the catalog's config has no key for it and it is not
served.
"""

from __future__ import annotations

import dataclasses
import functools
import importlib

import jax
import jax.numpy as jnp


def _router_width(config: dict) -> int:
    return (config.get("published") or {}).get("num_experts",
                                                config["num_experts"])


# ---------------------------------------------------------------- builder
def program_config(config: dict):
    """The published keys of a configuration file -> the program's
    ``TransformerConfig``, with the expert share the file states.  Where
    the file names the program's own constant for this model
    (``program_config``), the two must agree on everything but depth and
    the share: a width that drifted in the program is an error here."""
    from vllm_omni_tpu.models.common.transformer import TransformerConfig
    from vllm_omni_tpu.models.qwen3_next import expert_share

    cfg = expert_share(TransformerConfig(
        vocab_size=config["vocab_size"],
        hidden_size=config["hidden_size"],
        num_layers=config["num_hidden_layers"],
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"],
        intermediate_size=config["intermediate_size"],
        rope_theta=float(config["rope_theta"]),
        rms_eps=config["rms_norm_eps"],
        qk_norm=True,
        tie_word_embeddings=bool(config.get("tie_word_embeddings", False)),
        moe=True,
        num_experts=_router_width(config),
        num_experts_per_tok=config["num_experts_per_tok"],
        moe_intermediate_size=config["moe_intermediate_size"],
        moe_renormalize=bool(config["norm_topk_prob"]),
        shared_expert_size=config["shared_expert_intermediate_size"],
        full_attention_interval=config["full_attention_interval"],
        linear_num_key_heads=config["linear_num_key_heads"],
        linear_num_value_heads=config["linear_num_value_heads"],
        linear_key_head_dim=config["linear_key_head_dim"],
        linear_value_head_dim=config["linear_value_head_dim"],
        linear_conv_kernel_dim=config["linear_conv_kernel_dim"],
        attn_output_gate=True,
        partial_rotary_factor=config["partial_rotary_factor"],
        norm_zero_centered=True,
        residual_in_fp32=True,
    ), held=config["num_experts"], offset=config.get("expert_offset", 0))
    if config.get("program_config"):
        module, _, name = config["program_config"].partition(":")
        theirs = expert_share(
            dataclasses.replace(
                getattr(importlib.import_module(module), name),
                num_layers=cfg.num_layers),
            held=cfg.num_experts, offset=cfg.expert_offset)
        if theirs != cfg:
            raise SystemExit(
                f"benchmark: {config['program_config']} differs from the "
                f"published configuration: {theirs} != {cfg}")
    return cfg


#: What the routers' matrices are multiplied by after the program's
#: initialiser drew them like every other matrix (uniform in
#: +-1/sqrt(hidden): logits of deviation 0.58, a softmax so flat that the
#: ten picks weigh a tenth each and the tenth and eleventh lie 0.02 apart).
#: Under such a router any rounding upstream swaps a pick for another, and
#: a swap replaces a tenth of the routed sum (with a quarter of the experts
#: held here, four tenths of this share's) at one stroke: the served
#: logprobs then read 0.045 rms from the float32 reference, with tails past
#: 0.2.  Times 8 the logits deviate by 4.6, the first pick weighs about
#: 0.6 and the tenth 0.01, as a trained router's picks fall off, and a swap
#: at the tenth place moves a hundredth: 0.020 rms, flat between 5 and 10;
#: at 12 and more the sharper softmax amplifies the logits' own error
#: (PERF.md, Findings, PR 28).  A power of two: the products are exact.
ROUTER_GAIN = 8.0


def init_weights(key, cfg, dtype):
    from vllm_omni_tpu.models.common import transformer as tfm

    params = tfm.init_params(key, cfg=cfg, dtype=dtype)
    for layer in params["layers"]:
        w = layer["router"]["w"]
        layer["router"]["w"] = w * jnp.asarray(ROUTER_GAIN, w.dtype)
    return params


def param_specs(shapes):
    raise SystemExit("benchmark: the Qwen3-Next state cache has no "
                     "tensor-parallel layout; tensor_parallel_size is 1")


# -------------------------------------------------------------- reference
#: experts upcast to float32 at a time: bounds the transient to
#: 16 x (2048 x 1024 + 512 x 2048) x 4 B = 0.2 GB beside a full chip
EXPERT_CHUNK = 16


def _f32(a):
    return a.astype(jnp.float32)


def _rms1(x, w, eps):
    """The model's norm: zero-centred weight, applied as (1 + w)."""
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * (1.0 + w)


def _partial_rope(x, positions, theta, rot):
    """x [T, H, D]; rotate-half on dims 0..rot-1, the rest passed."""
    half = rot // 2
    inv_freq = 1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:rot]
    # stack + reshape (not concatenate) for the rotated halves, and a
    # block-wise join with the rest: see benchmark/archs/qwen3_moe.py _rope
    turned = jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                       axis=-2).reshape(*x.shape[:-1], rot)
    blocks = x.reshape(*x.shape[:-1], x.shape[-1] // rot, rot)
    return jnp.stack([turned] + [blocks[..., i, :] for i in
                                 range(1, x.shape[-1] // rot)],
                     axis=-2).reshape(x.shape)


def _moe(layer, x, h, hp):
    """x + the sparse MLP of normed rows h: the held experts, all computed
    for every token and masked by the router's weights, and the shared
    expert."""
    eps, top_k, renorm, offset = hp
    t = h.shape[0]
    probs = jax.nn.softmax(h @ _f32(layer["router"]["w"]), -1)
    top_w, top_i = jax.lax.top_k(probs, top_k)
    if renorm:
        top_w = top_w / jnp.sum(top_w, -1, keepdims=True)
    combine = jnp.zeros_like(probs).at[jnp.arange(t)[:, None], top_i].set(
        top_w)
    held = layer["experts"]["gate_up"].shape[0]
    combine = combine[:, offset:offset + held]
    y = jnp.zeros_like(x)
    for e0 in range(0, held, EXPERT_CHUNK):
        sl = slice(e0, e0 + EXPERT_CHUNK)
        gu = jnp.einsum("th,ehf->etf", h, _f32(layer["experts"]["gate_up"][sl]))
        gate, up = jnp.split(gu, 2, axis=-1)
        out = jnp.einsum("etf,efh->eth", jax.nn.silu(gate) * up,
                         _f32(layer["experts"]["down"][sl]))
        y = y + jnp.einsum("eth,te->th", out, combine[:, sl])
    gate, up = jnp.split(h @ _f32(layer["shared_expert"]["gate_up"]["w"]),
                         2, axis=-1)
    shared = (jax.nn.silu(gate) * up) @ _f32(
        layer["shared_expert"]["down"]["w"])
    return x + y + jax.nn.sigmoid(h @ _f32(layer["shared_gate"]["w"])) * shared


@functools.partial(jax.jit, static_argnames=("hp",))
def _full_layer(layer, x, hp):
    """One gated-attention block on x [T, hidden] float32; ``hp`` =
    (head_dim, rotary dims, theta, eps, top_k, renormalise, offset)."""
    head_dim, rot, theta, eps, top_k, renorm, offset = hp
    t = x.shape[0]
    pos = jnp.arange(t)
    h = _rms1(x, _f32(layer["input_norm"]["w"]), eps)
    qg = (h @ _f32(layer["q_proj"]["w"])).reshape(t, -1, 2 * head_dim)
    q, gate = qg[..., :head_dim], qg[..., head_dim:]
    k = (h @ _f32(layer["k_proj"]["w"])).reshape(t, -1, head_dim)
    v = (h @ _f32(layer["v_proj"]["w"])).reshape(t, -1, head_dim)
    q = _rms1(q, _f32(layer["q_norm"]["w"]), eps)
    k = _rms1(k, _f32(layer["k_norm"]["w"]), eps)
    q, k = _partial_rope(q, pos, theta, rot), _partial_rope(k, pos, theta, rot)
    group = q.shape[1] // k.shape[1]
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    s = jnp.einsum("thd,shd->hts", q, k) / jnp.sqrt(float(head_dim))
    s = jnp.where(pos[None, :, None] >= pos[None, None, :], s, -jnp.inf)
    o = jnp.einsum("hts,shd->thd", jax.nn.softmax(s, -1), v)
    o = o * jax.nn.sigmoid(gate)
    x = x + o.reshape(t, -1) @ _f32(layer["o_proj"]["w"])
    h = _rms1(x, _f32(layer["post_norm"]["w"]), eps)
    return _moe(layer, x, h, (eps, top_k, renorm, offset))


@functools.partial(jax.jit, static_argnames=("hp",))
def _linear_layer(layer, x, hp):
    """One Gated DeltaNet block on x [T, hidden] float32, the recurrence
    token by token; ``hp`` = (Hk, Hv, Dk, Dv, eps, top_k, renormalise,
    offset)."""
    n_k, n_v, d_k, d_v, eps, top_k, renorm, offset = hp
    la = layer["linear_attn"]
    t = x.shape[0]
    h = _rms1(x, _f32(layer["input_norm"]["w"]), eps)
    mixed = h @ _f32(la["in_proj_qkv"]["w"])
    z = (h @ _f32(la["in_proj_z"]["w"])).reshape(t, n_v, d_v)
    ba = h @ _f32(la["in_proj_ba"]["w"])
    # the convolution as a sum of K shifted products, zeros before token 0
    w = _f32(la["conv"]["w"])                                    # [K, C]
    kk = w.shape[0]
    padded = jnp.pad(mixed, ((kk - 1, 0), (0, 0)))
    mixed = jax.nn.silu(sum(padded[j:j + t] * w[j] for j in range(kk)))
    key_dim = n_k * d_k
    q = mixed[:, :key_dim].reshape(t, n_k, d_k)
    k = mixed[:, key_dim:2 * key_dim].reshape(t, n_k, d_k)
    v = mixed[:, 2 * key_dim:].reshape(t, n_v, d_v)
    unit = lambda a: a * jax.lax.rsqrt(  # noqa: E731
        jnp.sum(a * a, -1, keepdims=True) + 1e-6)
    q = jnp.repeat(unit(q) * d_k ** -0.5, n_v // n_k, axis=1)
    k = jnp.repeat(unit(k), n_v // n_k, axis=1)
    beta = jax.nn.sigmoid(ba[:, :n_v])
    g = -jnp.exp(_f32(la["A_log"])) * jax.nn.softplus(
        ba[:, n_v:] + _f32(la["dt_bias"]))

    def token(state, xs):                       # state [Hv, Dk, Dv]
        q_t, k_t, v_t, g_t, b_t = xs
        state = jnp.exp(g_t)[:, None, None] * state
        u = b_t[:, None] * (v_t - jnp.einsum("hkv,hk->hv", state, k_t))
        state = state + k_t[:, :, None] * u[:, None, :]
        return state, jnp.einsum("hkv,hk->hv", state, q_t)

    _, o = jax.lax.scan(token, jnp.zeros((n_v, d_k, d_v), jnp.float32),
                        (q, k, v, g, beta))
    o = _f32(la["norm"]["w"]) * o * jax.lax.rsqrt(
        jnp.mean(o * o, -1, keepdims=True) + eps) * jax.nn.silu(z)
    x = x + o.reshape(t, -1) @ _f32(la["out_proj"]["w"])
    h = _rms1(x, _f32(layer["post_norm"]["w"]), eps)
    return _moe(layer, x, h, (eps, top_k, renorm, offset))


@jax.jit
def _head(params, x, eps):
    h = _rms1(x, _f32(params["final_norm"]["w"]), eps)
    return jax.nn.log_softmax(h @ _f32(params["lm_head"]["w"]), -1)


def reference_logprobs(params, config: dict, token_ids: list[int],
                       last_n: int, tp: int = 1) -> jax.Array:
    """Log-softmax over the vocabulary after the last ``last_n`` positions
    of ``token_ids``: [last_n, vocab] float32.  ``params`` is the served
    weight tree (upcast here, layer by layer, the experts in blocks);
    ``config`` holds the keys of the configuration file."""
    if tp != 1:
        raise SystemExit("benchmark: the Qwen3-Next reference has no "
                         "tensor-parallel weight layout")
    eps = config["rms_norm_eps"]
    moe_hp = (eps, config["num_experts_per_tok"],
              bool(config["norm_topk_prob"]), config.get("expert_offset", 0))
    full_hp = (config["head_dim"],
               int(config["head_dim"] * config["partial_rotary_factor"]),
               float(config["rope_theta"])) + moe_hp
    linear_hp = (config["linear_num_key_heads"],
                 config["linear_num_value_heads"],
                 config["linear_key_head_dim"],
                 config["linear_value_head_dim"]) + moe_hp
    ids = jnp.asarray(token_ids, jnp.int32)
    with jax.default_matmul_precision("highest"):
        x = params["embed"]["w"][ids].astype(jnp.float32)
        for layer in params["layers"]:
            if "linear_attn" in layer:
                x = _linear_layer(layer, x, linear_hp)
            else:
                x = _full_layer(layer, x, full_hp)
        return _head({k: params[k] for k in ("final_norm", "lm_head")},
                     x[-last_n:], eps)


# ----------------------------------------------------------------- counts
def model_costs(config: dict, dtype_bytes: int = 2) -> dict:
    """Operations and bytes the decoder's algorithm needs on this chip,
    from the sizes of the configuration file (``num_experts`` held here,
    the router at its published width)."""
    h, d = config["hidden_size"], config["head_dim"]
    q_dim = config["num_attention_heads"] * d
    kv_dim = config["num_key_value_heads"] * d
    layers = config["num_hidden_layers"]
    full = layers // config["full_attention_interval"]
    n_k, n_v = config["linear_num_key_heads"], config["linear_num_value_heads"]
    d_k, d_v = config["linear_key_head_dim"], config["linear_value_head_dim"]
    conv_dim, v_dim = 2 * n_k * d_k + n_v * d_v, n_v * d_v
    kk = config["linear_conv_kernel_dim"]
    held, width = config["num_experts"], _router_width(config)
    shared = config["shared_expert_intermediate_size"]
    # a gated-attention layer: q_proj twice as wide (q and gate)
    attn_params = 2 * h * q_dim + 2 * h * kv_dim + q_dim * h
    gdn_params = h * (conv_dim + v_dim + 2 * n_v) + v_dim * h
    fixed_mlp = h * width + 3 * h * shared + h
    expert_params = 3 * h * config["moe_intermediate_size"]
    # of a token's top-k picks, those expected to land on a held expert
    picks_here = config["num_experts_per_tok"] * held / width
    # the rule, per token and value head: the decay of S, S^T k, the
    # rank-one update and S^T q
    rule_flops = n_v * 7.0 * d_k * d_v
    gdn_flops = 2.0 * kk * conv_dim + rule_flops + 12.0 * v_dim
    return {
        "layers": layers, "full": full, "linear": layers - full,
        "held": held, "width": width,
        "top_k": config["num_experts_per_tok"],
        "q_dim": q_dim, "conv_dim": conv_dim, "v_dim": v_dim, "n_v": n_v,
        "flops_per_token": (
            2.0 * (full * attn_params + (layers - full) * gdn_params
                   + layers * (fixed_mlp + picks_here * expert_params))
            + (layers - full) * gdn_flops),
        "gdn_flops_per_token": gdn_flops,
        "moe_flops_per_token": 2.0 * (fixed_mlp + picks_here * expert_params),
        "attn_flops_per_pair": 4.0 * q_dim * full,
        "head_flops_per_row": 2.0 * h * config["vocab_size"],
        "fixed_weight_bytes": dtype_bytes * (
            full * attn_params + (layers - full) * (gdn_params + kk * conv_dim)
            + layers * fixed_mlp + h * config["vocab_size"]),
        "fixed_mlp_bytes": dtype_bytes * fixed_mlp,
        "expert_bytes": dtype_bytes * expert_params,
        "kv_bytes_per_pos": dtype_bytes * 2 * full * kv_dim,
        # one sequence's state in one linear layer: the float32 recurrent
        # state and the convolution's K - 1 carried inputs
        "state_bytes": 4 * n_v * d_k * d_v + dtype_bytes * (kk - 1) * conv_dim,
    }


def _touched(costs: dict, tokens: float) -> float:
    """Held experts that ``tokens`` tokens are expected to route to."""
    return costs["held"] * (
        1.0 - (1.0 - costs["top_k"] / costs["width"]) ** tokens)


def _state_rows(step: dict) -> int:
    """Sequences whose state the step read and wrote (the flight record's
    ``state_rows``; a record without it has one a row)."""
    return step.get("state_rows", step["prefills"] + step["decodes"])


def step_cost(step: dict, config: dict, mean_prefill_ctx: float,
              mean_decode_ctx: float, dtype_bytes: int = 2
              ) -> tuple[float, float] | None:
    """One whole step (``prefill_tokens``, ``decodes``, ``prefills``), as
    ``qwen3_moe.step_cost`` counts it: every weight it touches read once
    (of the held experts as many as its tokens are expected to route to),
    attention's pairs and cache reads in the softmax layers only, and in
    each linear layer every touched sequence's state read and written
    once."""
    costs = model_costs(config, dtype_bytes)
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
              + costs["layers"] * _touched(costs, tokens)
              * costs["expert_bytes"]
              + costs["kv_bytes_per_pos"] * (kv_read + tokens)
              + costs["linear"] * 2.0 * _state_rows(step)
              * costs["state_bytes"])
    return flops, nbytes


def attn_cost(step: dict, config: dict, dtype_bytes: int = 2
              ) -> tuple[float, float]:
    """The attention of one step, as ``qwen3_moe.attn_cost`` counts it,
    over the softmax-attention layers only; the output gate (read, and the
    output scaled in place) rides with it under ``omni.attn``."""
    costs = model_costs(config, dtype_bytes)
    tokens = step["prefill_tokens"] + step["decodes"]
    kv_read = step["decode_ctx"]
    if step["prefill_tokens"] > 0:
        kv_read += (step["prefills"] * step["prefill_ctx"]
                    / step["prefill_tokens"] + step["prefill_tokens"] / 2.0)
    flops = (costs["attn_flops_per_pair"] * (step["prefill_ctx"]
                                             + step["decode_ctx"])
             + 4.0 * costs["full"] * tokens * costs["q_dim"])
    nbytes = (costs["kv_bytes_per_pos"] * kv_read
              + 3.0 * dtype_bytes * costs["full"] * tokens * costs["q_dim"])
    return flops, nbytes


def moe_cost(step: dict, config: dict, dtype_bytes: int = 2
             ) -> tuple[float, float]:
    """The sparse MLP of one step: the router at its whole width, the
    picks that land on held experts, the shared expert and its gate; the
    router's and the shared expert's weights and as many held experts as
    the step's tokens are expected to touch, read once a layer, and the
    activations in and out."""
    costs = model_costs(config, dtype_bytes)
    tokens = step["prefill_tokens"] + step["decodes"]
    flops = costs["layers"] * tokens * costs["moe_flops_per_token"]
    nbytes = costs["layers"] * (
        _touched(costs, tokens) * costs["expert_bytes"]
        + costs["fixed_mlp_bytes"]
        + 2.0 * dtype_bytes * tokens * config["hidden_size"])
    return flops, nbytes


def gdn_cost(step: dict, config: dict, dtype_bytes: int = 2
             ) -> tuple[float, float]:
    """What runs under ``omni.gdn`` in one step, by the recurrent
    algorithm: the convolution, the gates, per token and value head the
    decay, ``S^T k``, the rank-one update and ``S^T q``, and the gated
    norm.  Bytes: each touched sequence's state read and written once a
    layer, the convolution's weights, and the rows' activations (the
    mixed q/k/v rows and z in, the output out, the two gates)."""
    costs = model_costs(config, dtype_bytes)
    tokens = step["prefill_tokens"] + step["decodes"]
    flops = costs["linear"] * tokens * costs["gdn_flops_per_token"]
    nbytes = costs["linear"] * (
        2.0 * _state_rows(step) * costs["state_bytes"]
        + dtype_bytes * config["linear_conv_kernel_dim"] * costs["conv_dim"]
        + tokens * (dtype_bytes * (costs["conv_dim"] + 2 * costs["v_dim"])
                    + 8.0 * costs["n_v"]))
    return flops, nbytes
