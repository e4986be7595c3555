"""The granite-4.0-h-small decoder (``model_type`` ``granitemoehybrid``), as
a configuration file names it: ``"arch":
"benchmark.archs.granite_moe_hybrid"``.  It keeps the contract of
``benchmark/archs/qwen3_moe.py`` (``program_config``, ``init_weights``,
``param_specs``, ``reference_logprobs``, ``step_cost``, ``attn_cost``,
``moe_cost``) and adds ``ssd_cost`` for the roofline of the Mamba-2 layers.

The model (x a row of ``hidden_size``; ``rms(x, w) = x * rsqrt(mean(x^2) +
rms_norm_eps) * w``, plain weight).  ``x_0 = embedding_multiplier * E[ids]``.
Every layer is the block ``x = x + residual_multiplier * mixer(rms(x,
w_1)); x = x + residual_multiplier * (moe(rms(x, w_2)) + shared(rms(x,
w_2)))``; after the last layer ``rms(x, w_f) E^T / logits_scaling``.
``layer_types`` says which mixer:

*Mamba-2* (``"mamba"``).  ``[z | xBC | dt] = h W_in`` (``mamba_n_heads *
mamba_d_head`` | that + ``2 * mamba_d_state`` | ``mamba_n_heads``, no bias);
``xBC = silu(conv(xBC) + b_conv)``, depth-wise, causal, ``mamba_d_conv`` taps,
zeros before a sequence's first token; ``[u | B | C] = xBC`` with ``u`` as
heads of ``mamba_d_head`` and ``B``, ``C`` of ``mamba_d_state`` shared by all
heads (``mamba_n_groups`` 1); ``dt = softplus(dt + dt_bias)`` (no clamp), ``a
= -exp(A_log) * dt`` a head.  A head's state ``S`` is ``[d_head, d_state]``:
``S_t = exp(a_t) S_{t-1} + dt_t u_t B_t^T``, ``y_t = S_t C_t + D u_t``.  Then
``y = y * silu(z)`` (the gate BEFORE the norm), ``y = w * y * rsqrt(mean(y^2)
+ eps)`` over all heads at once, ``out = y W_out``.

*Attention* (``"attention"``).  ``q, k, v`` by three matrices without bias,
``num_attention_heads`` / ``num_key_value_heads`` heads of ``hidden /
num_attention_heads``; **nothing added or rotated for position**
(``position_embedding_type`` ``nope``); causal softmax of ``q k^T *
attention_multiplier``; ``W_o``.

*MLP*, every layer.  ``l = h W_r`` over the router's whole width in float32;
the ``num_experts_per_tok`` largest kept; ``g = softmax`` over those kept
logits; ``sum_e g_e W2_e (silu(h W1_e[:, :I]) * (h W1_e[:, I:]))`` over the
kept experts HELD here; ``shared`` the same SwiGLU at
``shared_intermediate_size``, ungated, added.

The reference is straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``: the recurrence as a
``lax.scan`` over tokens (NOT the chunked form: it shares no algebra with
the served path), the convolution as a sum of four shifted products,
attention as a dense masked softmax a block of query rows at a time, every
held expert computed for every token and weighted by the router, the head a
block of the vocabulary at a time (so that the check's 2568 tokens fit
beside a full chip), no cache, no kernel, no batching.  It shares only the
names of the weight tree with the program (whose modules are imported
inside the builder functions alone); the sizes come from the configuration
file's keys.

The chip's share: the file's ``num_local_experts`` counts the experts HELD
here (ids ``expert_offset .. expert_offset + num_local_experts``), its
``published.num_local_experts`` is the router's width.  A pick that lives on
another chip adds nothing, in the program and here alike, and that partial
result goes on to the next layer (model-configs guide, section 4).
"""

from __future__ import annotations

import dataclasses
import functools
import importlib

import jax
import jax.numpy as jnp

from benchmark.archs import glm4_moe_lite

#: the file's names for the kinds of layer -> the program's
KINDS = {"mamba": "mamba", "attention": "full_attention"}
#: what the file must say for the program to be the model: each is a switch
#: the family has and this model leaves one way
BUILT = {"mamba_n_groups": 1, "mamba_conv_bias": True,
         "mamba_proj_bias": False, "attention_bias": False,
         "position_embedding_type": "nope", "hidden_act": "silu",
         "normalization_function": "rmsnorm", "tie_word_embeddings": True}


def _router_width(config: dict) -> int:
    return (config.get("published") or {}).get("num_local_experts",
                                                config["num_local_experts"])


def _layer_mix(config: dict) -> dict:
    """Layers of each kind among the file's ``num_hidden_layers``, in the
    mix its ``stage_layers`` state (the period served here: nine Mamba-2
    layers to one attention).  At the file's own depth these are its
    layers; a model twice as deep has those of two such periods, so that
    every count is linear in depth, as the harness's contract has it."""
    stage = config["stage_layers"]
    share = config["num_hidden_layers"] / sum(stage.values())
    return {k: v * share for k, v in stage.items()}


# ---------------------------------------------------------------- builder
def program_config(config: dict):
    """The published keys of a configuration file -> the program's
    ``TransformerConfig``, with the expert share the file states.  Where
    the file names the program's own constant for this model
    (``program_config``), the two must agree on everything but depth and
    the share: a width or a multiplier that drifted in the program is an
    error here."""
    from vllm_omni_tpu.models.common.transformer import TransformerConfig
    from vllm_omni_tpu.models.granite_moe_hybrid import expert_share

    for key, want in BUILT.items():
        if config[key] != want:
            raise SystemExit(f"benchmark: {key} {config[key]!r} is not "
                             f"built (only {want!r})")
    heads, d_head = config["mamba_n_heads"], config["mamba_d_head"]
    if heads * d_head != config["mamba_expand"] * config["hidden_size"]:
        raise SystemExit("benchmark: mamba_n_heads x mamba_d_head is not "
                         "mamba_expand x hidden_size")
    kinds = tuple(KINDS[k] for k in config["layer_types"])
    cfg = expert_share(TransformerConfig(
        vocab_size=config["vocab_size"],
        hidden_size=config["hidden_size"],
        num_layers=config["num_hidden_layers"],
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        head_dim=config["hidden_size"] // config["num_attention_heads"],
        intermediate_size=config["intermediate_size"],
        rope_theta=float(config["rope_theta"]),
        rms_eps=config["rms_norm_eps"],
        tie_word_embeddings=True,
        moe=True,
        num_experts=_router_width(config),
        num_experts_per_tok=config["num_experts_per_tok"],
        moe_intermediate_size=config["intermediate_size"],
        moe_renormalize=True,
        shared_expert_size=config["shared_intermediate_size"],
        shared_expert_gate=False,
        layer_kinds=kinds,
        mamba_num_heads=heads,
        mamba_head_dim=d_head,
        mamba_state_dim=config["mamba_d_state"],
        mamba_conv_kernel=config["mamba_d_conv"],
        mamba_chunk_size=config["mamba_chunk_size"],
        use_rope=False,
        attention_multiplier=float(config["attention_multiplier"]),
        embedding_multiplier=float(config["embedding_multiplier"]),
        residual_multiplier=float(config["residual_multiplier"]),
        logits_scaling=float(config["logits_scaling"]),
    ), held=config["num_local_experts"],
        offset=config.get("expert_offset", 0))
    stage = config["stage_layers"]
    served = {"mamba": kinds.count("mamba"),
              "attention": kinds.count("full_attention")}
    if len(kinds) != cfg.num_layers or served != stage:
        raise SystemExit(f"benchmark: stage_layers {stage} are not the "
                         f"{cfg.num_layers} layers served, {served}")
    if config.get("program_config"):
        module, _, name = config["program_config"].partition(":")
        theirs = getattr(importlib.import_module(module), name)
        theirs = expert_share(dataclasses.replace(
            theirs, num_layers=cfg.num_layers,
            layer_kinds=theirs.layer_kinds[:cfg.num_layers]),
            held=cfg.num_experts, offset=cfg.expert_offset)
        if theirs != cfg:
            raise SystemExit(
                f"benchmark: {config['program_config']} differs from the "
                f"published configuration: {theirs} != {cfg}")
    return cfg


#: The routers' matrices times this after the plain draw, the cure of a
#: softmax router that ``qwen3_next.ROUTER_GAIN`` is (its comment has the
#: readings): the ten kept logits then fall off as a trained router's do,
#: and a rounding that swaps the tenth pick for the eleventh moves a
#: hundredth of the routed sum, not a tenth.  A power of two.
ROUTER_GAIN = 8.0
#: ``q_proj`` and ``k_proj`` times this each.  Under the published scale
#: 1/128 the plain draw's scores deviate by 0.03 (q and k entries of
#: deviation 0.58 over 128 dims, / 128): a uniform softmax, a mean of the
#: values that is blind to the keys and to the scale, so that the one
#: attention layer would add nothing a check can see.  Times 8 each the
#: scores deviate by about 1.9, as under a trained model whose q and k
#: grew to meet the scale.  A power of two.
QK_GAIN = 8.0
#: The embedding's rows times ``EMBED_SHRINK`` and the final norm's weight
#: times ``HEAD_GAIN``.  The head is the embedding transposed over 16: with
#: rows of deviation 0.02 a unit row of the final norm gives logits of
#: deviation 0.08, a softmax so flat that every served logprob reads -11.5
#: whatever the layers computed, while ``x_0 = 12 E[id]`` (0.24 rms, half
#: the stream) puts the input token itself 28 deviations above the rest.
#: Rows an eighth as large leave ``x_0`` 0.03 rms of a stream that the
#: twenty branches bring to about 0.45 (the self logit 4 deviations up:
#: about the largest of 100 352 draws), and a final-norm weight of 64
#: gives the logits a deviation of 0.64, the other configurations' 0.58.
#: The multipliers stay 12, 0.22, 1/128 and 1/16.  Powers of two.
EMBED_SHRINK = 0.125
HEAD_GAIN = 64.0


def init_weights(key, cfg, dtype):
    """The program's own draw (matrices and taps uniform in +-1/sqrt(fan
    in), the convolution's bias likewise, norm weights ones; ``A_log``,
    ``dt_bias`` and ``D`` as Mamba-2's initialiser: A in 1..16, dt in
    1e-3..1e-1 through the inverse softplus, D ones), the routed experts
    of a layer related as ``glm4_moe_lite.init_weights`` relates them and
    for its reason (``EXPERT_OWN_SHARE``: 2.5 of a token's ten picks land
    on this share, and a swapped pick would otherwise replace its part by
    an unrelated function; the CPU tests hold single picks with unrelated
    experts), and the four gains above, each with its reason."""
    params = glm4_moe_lite.init_weights(key, cfg, dtype)

    def times(leaf, gain):
        return leaf * jnp.asarray(gain, leaf.dtype)

    params["embed"]["w"] = times(params["embed"]["w"], EMBED_SHRINK)
    params["final_norm"]["w"] = times(params["final_norm"]["w"], HEAD_GAIN)
    for layer in params["layers"]:
        layer["router"]["w"] = times(layer["router"]["w"], ROUTER_GAIN)
        for name in ("q_proj", "k_proj"):
            if name in layer:
                layer[name]["w"] = times(layer[name]["w"], QK_GAIN)
    return params


def param_specs(shapes):
    raise SystemExit("benchmark: the state cache of a model with Mamba-2 "
                     "layers has no tensor-parallel layout; "
                     "tensor_parallel_size is 1")


# -------------------------------------------------------------- reference
#: experts upcast to float32 at a time: bounds the transient to
#: 6 x (4096 x 1536 + 768 x 4096) x 4 B = 0.23 GB of weights and
#: 6 x 2568 x (1536 + 768 + 4096) x 4 B = 0.39 GB of activations beside a
#: full chip
EXPERT_CHUNK = 6
#: query rows of the dense masked softmax computed at a time: 32 heads x
#: 512 x 2568 scores x 4 B = 0.17 GB
QUERY_BLOCK = 512
#: rows of the vocabulary the head takes at a time: 12 544 x 4096 x 4 B =
#: 0.2 GB of the embedding in float32, where the whole is 1.6 GB
VOCAB_BLOCK = 12544


def _f32(a):
    return a.astype(jnp.float32)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _swiglu(h, gate_up, down):
    gate, up = jnp.split(h @ _f32(gate_up), 2, axis=-1)
    return (jax.nn.silu(gate) * up) @ _f32(down)


def _mlp(layer, h, top_k, offset):
    """The held experts' part of the routed sum for normed rows h, every
    held expert computed for every token and weighted by the router (zero
    where it did not pick the expert), plus the shared SwiGLU."""
    t = h.shape[0]
    logits = h @ _f32(layer["router"]["w"])
    top_l, top_i = jax.lax.top_k(logits, top_k)
    combine = jnp.zeros_like(logits).at[jnp.arange(t)[:, None], top_i].set(
        jax.nn.softmax(top_l, -1))
    held = layer["experts"]["gate_up"].shape[0]
    combine = combine[:, offset:offset + held]
    y = jnp.zeros_like(h)
    for e0 in range(0, held, EXPERT_CHUNK):
        sl = slice(e0, e0 + EXPERT_CHUNK)
        gu = jnp.einsum("th,ehf->etf", h, _f32(layer["experts"]["gate_up"][sl]))
        gate, up = jnp.split(gu, 2, axis=-1)
        out = jnp.einsum("etf,efh->eth", jax.nn.silu(gate) * up,
                         _f32(layer["experts"]["down"][sl]))
        y = y + jnp.einsum("eth,te->th", out, combine[:, sl])
    shared = layer["shared_expert"]
    return y + _swiglu(h, shared["gate_up"]["w"], shared["down"]["w"])


def _mamba(layer, h, dims, eps):
    """The Mamba-2 mixer of normed rows h [T, hidden], one sequence from
    its first token, the recurrence token by token."""
    n_h, d_h, n = dims
    mb = layer["mamba"]
    t, d_inner = h.shape[0], n_h * d_h
    zxbcdt = h @ _f32(mb["in_proj"]["w"])
    z = zxbcdt[:, :d_inner]
    xbc = zxbcdt[:, d_inner:2 * d_inner + 2 * n]
    dt = jax.nn.softplus(zxbcdt[:, 2 * d_inner + 2 * n:]
                         + _f32(mb["dt_bias"]))               # [T, H]
    w = _f32(mb["conv"]["w"])                     # [K, C], w[K-1] on t
    kk = w.shape[0]
    padded = jnp.pad(xbc, ((kk - 1, 0), (0, 0)))  # zeros before token 0
    xbc = jax.nn.silu(sum(padded[j:j + t] * w[j] for j in range(kk))
                      + _f32(mb["conv"]["b"]))
    u = xbc[:, :d_inner].reshape(t, n_h, d_h)
    b, c = xbc[:, d_inner:d_inner + n], xbc[:, d_inner + n:]
    a = -jnp.exp(_f32(mb["A_log"])) * dt

    def token(state, xs):                         # state [H, P, N]
        u_t, dt_t, a_t, b_t, c_t = xs
        state = jnp.exp(a_t)[:, None, None] * state \
            + (dt_t[:, None] * u_t)[:, :, None] * b_t[None, None, :]
        return state, jnp.einsum("hpn,n->hp", state, c_t)

    _, y = jax.lax.scan(token, jnp.zeros((n_h, d_h, n), jnp.float32),
                        (u, dt, a, b, c))
    y = y + _f32(mb["D"])[None, :, None] * u
    y = y.reshape(t, d_inner) * jax.nn.silu(z)
    return _rms(y, _f32(mb["norm"]["w"]), eps) @ _f32(mb["out_proj"]["w"])


def _attention(layer, h, head_dim, scale):
    """Grouped-query attention of normed rows h [T, hidden], one sequence,
    no positional encoding: a dense masked softmax, ``QUERY_BLOCK`` query
    rows at a time."""
    t = h.shape[0]
    pos = jnp.arange(t)
    q = (h @ _f32(layer["q_proj"]["w"])).reshape(t, -1, head_dim)
    k = (h @ _f32(layer["k_proj"]["w"])).reshape(t, -1, head_dim)
    v = (h @ _f32(layer["v_proj"]["w"])).reshape(t, -1, head_dim)
    group = q.shape[1] // k.shape[1]
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    outs = []
    for t0 in range(0, t, QUERY_BLOCK):
        rows = pos[t0:t0 + QUERY_BLOCK]
        s = jnp.einsum("thd,shd->hts", q[t0:t0 + QUERY_BLOCK], k) * scale
        s = jnp.where(rows[None, :, None] >= pos[None, None, :], s, -jnp.inf)
        outs.append(jnp.einsum("hts,shd->thd", jax.nn.softmax(s, -1), v))
    o = jnp.concatenate(outs, axis=0)
    return o.reshape(t, -1) @ _f32(layer["o_proj"]["w"])


@functools.partial(jax.jit, static_argnames=("hp",))
def _layer(layer, x, hp):
    """One block on x [T, hidden] float32; ``hp`` = (eps, residual
    multiplier, attention scale, head_dim, mamba dims, top_k, offset).
    What a layer is, its weights say."""
    eps, res, scale, head_dim, dims, top_k, offset = hp
    h = _rms(x, _f32(layer["input_norm"]["w"]), eps)
    if "mamba" in layer:
        x = x + res * _mamba(layer, h, dims, eps)
    else:
        x = x + res * _attention(layer, h, head_dim, scale)
    h = _rms(x, _f32(layer["post_norm"]["w"]), eps)
    return x + res * _mlp(layer, h, top_k, offset)


@jax.jit
def _head_block(h, rows):
    return h @ _f32(rows).T


def reference_logprobs(params, config: dict, token_ids: list[int],
                       last_n: int, tp: int = 1) -> jax.Array:
    """Log-softmax over the vocabulary after the last ``last_n`` positions
    of ``token_ids``: [last_n, vocab] float32.  ``params`` is the served
    weight tree (upcast here, layer by layer, the experts and the head in
    blocks); ``config`` holds the keys of the configuration file."""
    if tp != 1:
        raise SystemExit("benchmark: the Granite-H reference has no "
                         "tensor-parallel weight layout")
    eps = config["rms_norm_eps"]
    hp = (eps, float(config["residual_multiplier"]),
          float(config["attention_multiplier"]),
          config["hidden_size"] // config["num_attention_heads"],
          (config["mamba_n_heads"], config["mamba_d_head"],
           config["mamba_d_state"]),
          config["num_experts_per_tok"], config.get("expert_offset", 0))
    ids = jnp.asarray(token_ids, jnp.int32)
    embed = params["embed"]["w"]
    with jax.default_matmul_precision("highest"):
        x = float(config["embedding_multiplier"]) * _f32(embed[ids])
        for layer in params["layers"]:
            x = _layer(layer, x, hp)
        h = _rms(x[-last_n:], _f32(params["final_norm"]["w"]), eps)
        logits = jnp.concatenate(
            [_head_block(h, embed[v0:v0 + VOCAB_BLOCK])
             for v0 in range(0, embed.shape[0], VOCAB_BLOCK)], axis=-1)
        return jax.nn.log_softmax(logits / float(config["logits_scaling"]),
                                  -1)


# ----------------------------------------------------------------- counts
def model_costs(config: dict, dtype_bytes: int = 2) -> dict:
    """Operations and bytes the decoder's algorithm needs on this chip,
    from the sizes of the configuration file (``num_local_experts`` held
    here, the router at its published width)."""
    h = config["hidden_size"]
    heads = config["num_attention_heads"]
    d = h // heads
    q_dim, kv_dim = heads * d, config["num_key_value_heads"] * d
    mix = _layer_mix(config)
    layers = mix["mamba"] + mix["attention"]
    n_h, d_h = config["mamba_n_heads"], config["mamba_d_head"]
    n, kk = config["mamba_d_state"], config["mamba_d_conv"]
    d_inner = n_h * d_h
    conv_dim = d_inner + 2 * n
    held, width = config["num_local_experts"], _router_width(config)
    top_k = config["num_experts_per_tok"]
    attn_params = h * q_dim + 2 * h * kv_dim + q_dim * h
    mamba_proj = h * (d_inner + conv_dim + n_h) + d_inner * h
    # the taps and their bias, A_log, dt_bias and D, the gated norm
    mamba_small = (kk + 1) * conv_dim + 3 * n_h + d_inner
    fixed_mlp = h * width + 3 * h * config["shared_intermediate_size"]
    expert_params = 3 * h * config["intermediate_size"]
    # of a token's top-k picks, those expected to land on a held expert
    picks_here = top_k * held / width
    # the rule, per token and head, by the recurrent algorithm: the decay
    # of S, the rank-one update (a product and a sum) and S C (a product
    # and a sum); around it the taps, the bias and silu, softplus and the
    # decay, the skip, the gate and the norm
    rule_flops = n_h * 5.0 * d_h * n
    ssd_flops = (2.0 * kk + 6.0) * conv_dim + rule_flops + 12.0 * d_inner
    return {
        "mix": mix, "layers": layers, "held": held, "width": width,
        "top_k": top_k, "q_dim": q_dim, "conv_dim": conv_dim,
        "d_inner": d_inner, "n_h": n_h,
        "mamba_params": mamba_proj + mamba_small,
        "attn_params": attn_params, "fixed_mlp_params": fixed_mlp,
        "expert_params": expert_params,
        "flops_per_token": (
            2.0 * (mix["attention"] * attn_params + mix["mamba"] * mamba_proj
                   + layers * (fixed_mlp + picks_here * expert_params))
            + mix["mamba"] * ssd_flops),
        "ssd_flops_per_token": ssd_flops,
        "moe_flops_per_token": 2.0 * (fixed_mlp + picks_here * expert_params),
        # a pair a query head: the score over D numbers and the value over
        # D, a product and a sum each
        "attn_flops_per_pair": 4.0 * q_dim * mix["attention"],
        "head_flops_per_row": 2.0 * h * config["vocab_size"],
        "fixed_weight_bytes": dtype_bytes * (
            mix["attention"] * attn_params
            + mix["mamba"] * (mamba_proj + mamba_small)
            + layers * fixed_mlp + h * config["vocab_size"]),
        "fixed_mlp_bytes": dtype_bytes * fixed_mlp,
        "expert_bytes": dtype_bytes * expert_params,
        "ssd_weight_bytes": dtype_bytes * mamba_small,
        "kv_bytes_per_pos": dtype_bytes * 2 * kv_dim * mix["attention"],
        # one sequence's state in one Mamba-2 layer, float32: the rule's
        # [heads, d_head, d_state] and the convolution's K - 1 carried rows
        "state_bytes": 4 * n_h * d_h * n,
        "conv_state_bytes": 4 * (kk - 1) * conv_dim,
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
    attention's pairs and cache reads in the attention layer only, and in
    each Mamba-2 layer every touched sequence's state read and written
    once."""
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
              + costs["layers"] * _touched(costs, tokens)
              * costs["expert_bytes"]
              + costs["kv_bytes_per_pos"] * (kv_read + tokens)
              + mix["mamba"] * 2.0 * _state_rows(step)
              * (costs["state_bytes"] + costs["conv_state_bytes"]))
    return flops, nbytes


def attn_cost(step: dict, config: dict, dtype_bytes: int = 2
              ) -> tuple[float, float]:
    """The attention of one step, as ``qwen3_moe.attn_cost`` counts it,
    over the attention layer only: ``4 x 128 x ctx`` operations a query
    head, 4 096 B a cached token.  Bytes: every decode row reads its
    context once, a chunk reads what it attends once, and queries come in
    and outputs go out at the head's width."""
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
    """The MLP of one step (what runs under ``omni.moe``): the router at
    its whole width, the picks that land on held experts and the shared
    SwiGLU per token; the router's and the shared expert's weights and as
    many held experts as the step's tokens are expected to touch, read
    once a layer, and the activations in and out."""
    costs = model_costs(config, dtype_bytes)
    tokens = step["prefill_tokens"] + step["decodes"]
    flops = costs["layers"] * tokens * costs["moe_flops_per_token"]
    nbytes = costs["layers"] * (
        _touched(costs, tokens) * costs["expert_bytes"]
        + costs["fixed_mlp_bytes"]
        + 2.0 * dtype_bytes * tokens * config["hidden_size"])
    return flops, nbytes


def ssd_cost(step: dict, config: dict, dtype_bytes: int = 2
             ) -> tuple[float, float]:
    """What runs under ``omni.ssd`` in one step (the two projections are
    ``omni.proj``'s, the convolution rows' gathers and scatters
    ``omni.ssd_state``'s), by the recurrent algorithm: per token the taps,
    the bias and the activations, and a head the decay of ``S``, the
    rank-one update and ``S C`` (the chunked form a prompt's rows run
    does more products than these; they are not counted), the skip, the
    gate and the norm.  Bytes: every touched sequence's state and carried
    convolution rows read once and written once a layer, the small
    weights, and each token's row in (the projection's float32 ``[z | xBC
    | dt]``) and out (the normed ``y`` in the serve dtype)."""
    costs = model_costs(config, dtype_bytes)
    mamba = costs["mix"]["mamba"]
    tokens = step["prefill_tokens"] + step["decodes"]
    flops = mamba * tokens * costs["ssd_flops_per_token"]
    nbytes = mamba * (
        2.0 * _state_rows(step) * (costs["state_bytes"]
                                   + costs["conv_state_bytes"])
        + costs["ssd_weight_bytes"]
        + tokens * (4.0 * (costs["d_inner"] + costs["conv_dim"]
                           + costs["n_h"])
                    + dtype_bytes * costs["d_inner"]))
    return flops, nbytes
