"""The NVIDIA-Nemotron-3-Super-120B-A12B decoder (``model_type``
``nemotron_h``), as a configuration file names it: ``"arch":
"benchmark.archs.nemotron_h"``.  It keeps the contract of
``benchmark/archs/qwen3_moe.py`` (``program_config``, ``init_weights``,
``param_specs``, ``reference_logprobs``, ``step_cost``, ``attn_cost``,
``moe_cost``), ``ssd_cost`` of ``granite_moe_hybrid.py`` for the Mamba-2
layers, and adds ``moe_latent_cost`` for the two projections around the
latent experts.

The model (x a row of ``hidden_size``; ``rms(x, w) = x * rsqrt(mean(x^2) +
layer_norm_epsilon) * w``, plain weight).  ``x_0 = E[ids]``.  A layer is ONE
sublayer, ``x = x + f(rms(x, w))``, and the letter of
``hybrid_override_pattern`` says which ``f``; after the last layer ``rms(x,
w_f) W_head`` (untied).

*Mamba-2* (``M``).  ``[z | xBC | dt] = h W_in`` (``mamba_num_heads *
mamba_head_dim`` | that + ``2 * n_groups * ssm_state_size`` |
``mamba_num_heads``, no bias); ``xBC = silu(conv(xBC) + b_conv)``,
depth-wise, causal, ``conv_kernel`` taps, zeros before a sequence's first
token; ``[u | B | C] = xBC`` with ``u`` as heads of ``mamba_head_dim`` and
``B``, ``C`` as ``n_groups`` groups of ``ssm_state_size``: head ``h`` reads
group ``h // (heads / groups)``; ``dt = softplus(dt + dt_bias)`` (NO clamp:
a departure only if the family clamps, see ``assumed.dt_limits``), ``a =
-exp(A_log) * dt`` a head.  A head's state ``S`` is ``[head_dim, state]``:
``S_t = exp(a_t) S_{t-1} + dt_t u_t B_t^T``, ``y_t = S_t C_t + D u_t``.
Then ``y = y * silu(z)`` (the gate BEFORE the norm), ``y = w * y *
rsqrt(mean(y^2) + eps)`` over each group's ``heads * head_dim / groups``
channels, ``out = y W_out``.

*Attention* (``*``).  ``q, k, v`` by three matrices without bias,
``num_attention_heads`` / ``num_key_value_heads`` heads of ``head_dim``;
**nothing added or rotated for position** (the family's published code
builds no rotary table; ``rope_theta`` and ``partial_rotary_factor`` are in
the config and read by nothing: ``assumed.positions``); causal softmax of
``q k^T * head_dim ** -0.5``; ``W_o``.

*Latent experts* (``E``).  ``s = sigmoid(h W_g)`` over the router's whole
width in float32; the ``num_experts_per_tok`` largest of ``s +
e_score_correction_bias`` are picked (``n_group`` 1: no group limit); ``w_i
= routed_scaling_factor * s_i / sum_picked s``; ``l = h W_1`` (hidden ->
``moe_latent_size``); ``r = sum_i w_i D_i relu(U_i l)^2`` over the picked
experts HELD here (ungated, ``mlp_hidden_act`` relu2); ``out = r W_2 + D_s
relu(U_s h)^2`` with the shared expert on ``h`` itself.

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

The chip's share: the file's ``n_routed_experts`` counts the experts HELD
here (ids ``expert_offset .. expert_offset + n_routed_experts``), its
``published.n_routed_experts`` is the router's width; its ``vocab_size``
counts the rows of the embedding and of the head held here (ids from 0),
and traffic, logits and sampling are over that slice.  A pick that lives on
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

#: the letters of the file's ``hybrid_override_pattern`` -> the program's
#: kinds and the names of ``stage_layers``
KINDS = {"M": ("mamba", "mamba"), "*": ("full_attention", "attention"),
         "E": ("feed_forward", "moe")}
#: what the file must say for the program to be the model: each is a switch
#: the family has and this model leaves one way (``time_step_limit`` is not
#: in the published file: a file that states one states a clamp)
BUILT = {"mlp_bias": False, "use_bias": False, "mamba_proj_bias": False,
         "attention_bias": False, "use_conv_bias": True, "n_group": 1,
         "topk_group": 1, "moe_shared_expert_overlap": False,
         "mlp_hidden_act": "relu2", "mamba_hidden_act": "silu",
         "tie_word_embeddings": False, "norm_topk_prob": True,
         "n_shared_experts": 1, "residual_in_fp32": False,
         "sliding_window": None, "num_nextn_predict_layers": 0,
         "time_step_limit": None}


def _router_width(config: dict) -> int:
    return (config.get("published") or {}).get("n_routed_experts",
                                                config["n_routed_experts"])


def _layer_mix(config: dict) -> dict:
    """Layers of each kind among the file's ``num_hidden_layers``, in the
    mix its ``stage_layers`` state (the period served here: five Mamba-2
    and five expert layers to one attention).  At the file's own depth
    these are its layers; a model twice as deep has those of two such
    periods, so that every count is linear in depth, as the harness's
    contract has it."""
    stage = config["stage_layers"]
    share = config["num_hidden_layers"] / sum(stage.values())
    return {k: v * share for k, v in stage.items()}


# ---------------------------------------------------------------- builder
def program_config(config: dict):
    """The published keys of a configuration file -> the program's
    ``TransformerConfig``, with the expert share and the vocabulary slice
    the file states.  Where the file names the program's own constant for
    this model (``program_config``), the two must agree on everything but
    depth, the share and the slice: a width that drifted in the program is
    an error here."""
    from vllm_omni_tpu.models.common.transformer import TransformerConfig
    from vllm_omni_tpu.models.nemotron_h import expert_share

    for key, want in BUILT.items():
        if config.get(key) != want:
            raise SystemExit(f"benchmark: {key} {config.get(key)!r} is not "
                             f"built (only {want!r})")
    heads, d_head = config["mamba_num_heads"], config["mamba_head_dim"]
    if heads * d_head != config["expand"] * config["hidden_size"]:
        raise SystemExit("benchmark: mamba_num_heads x mamba_head_dim is "
                         "not expand x hidden_size")
    if config["layer_norm_epsilon"] != config["norm_eps"]:
        raise SystemExit("benchmark: layer_norm_epsilon and norm_eps differ")
    pattern = config["hybrid_override_pattern"]
    unknown = sorted(set(pattern) - set(KINDS))
    if unknown:
        raise SystemExit(f"benchmark: pattern letters {unknown} are not "
                         f"built (only {sorted(KINDS)})")
    kinds = tuple(KINDS[c][0] for c in pattern)
    cfg = expert_share(TransformerConfig(
        vocab_size=config["vocab_size"],
        hidden_size=config["hidden_size"],
        num_layers=config["num_hidden_layers"],
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"],
        intermediate_size=config["intermediate_size"],
        rope_theta=float(config["rope_theta"]),
        rms_eps=config["layer_norm_epsilon"],
        tie_word_embeddings=False,
        moe=True,
        num_experts=_router_width(config),
        num_experts_per_tok=config["num_experts_per_tok"],
        moe_intermediate_size=config["moe_intermediate_size"],
        moe_renormalize=True,
        shared_expert_size=config["moe_shared_expert_intermediate_size"],
        shared_expert_gate=False,
        router_scoring="sigmoid",
        router_bias=True,
        routed_scaling_factor=float(config["routed_scaling_factor"]),
        mlp_gated=False,
        mlp_activation=config["mlp_hidden_act"],
        moe_latent_size=config["moe_latent_size"],
        layer_kinds=kinds,
        mamba_num_heads=heads,
        mamba_head_dim=d_head,
        mamba_state_dim=config["ssm_state_size"],
        mamba_conv_kernel=config["conv_kernel"],
        mamba_chunk_size=config["chunk_size"],
        mamba_n_groups=config["n_groups"],
        use_rope=False,
    ), held=config["n_routed_experts"],
        offset=config.get("expert_offset", 0))
    stage = config["stage_layers"]
    served = {name: pattern.count(c) for c, (_, name) in KINDS.items()}
    if len(kinds) != cfg.num_layers or served != stage:
        raise SystemExit(f"benchmark: stage_layers {stage} are not the "
                         f"{cfg.num_layers} layers served, {served}")
    whole = (config.get("published") or {}).get("hybrid_override_pattern")
    if whole is not None and pattern not in whole:
        raise SystemExit(f"benchmark: the pattern {pattern!r} is no run of "
                         "the published pattern's layers")
    if config.get("program_config"):
        module, _, name = config["program_config"].partition(":")
        theirs = getattr(importlib.import_module(module), name)
        theirs = expert_share(dataclasses.replace(
            theirs, num_layers=cfg.num_layers, layer_kinds=kinds,
            vocab_size=cfg.vocab_size),
            held=cfg.num_experts, offset=cfg.expert_offset)
        if theirs != cfg:
            raise SystemExit(
                f"benchmark: {config['program_config']} differs from the "
                f"published configuration: {theirs} != {cfg}")
    return cfg


#: ``q_proj`` and ``k_proj`` times this each.  Under ``head_dim ** -0.5``
#: the plain draw's scores deviate by 0.34 (q and k entries of deviation
#: 0.58 over 128 dims, / 11.3): over the check's 2 560 keys that is a
#: nearly uniform softmax, a mean of the values that hardly knows the keys,
#: so that the one attention layer would add a hundredth of what a Mamba-2
#: layer adds and nothing a check can see.  Times 2 each the scores deviate
#: by 1.4, the weight of the likeliest key about a hundred times the
#: typical one's, as under a trained model.  A power of two.
QK_GAIN = 2.0


#: Tokens of the calibration pass that balances the routers (below), one
#: sequence: each of 512 experts' 95.7th percentile has 88 samples above
#: it, and the positions reach where the traffic's contexts lie (what the
#: layers' outputs share drifts with the position: balanced over 512
#: tokens, the held share of the check's 2 560 still read 22.4-27.7% a
#: layer; my chip runs, PR 46)
CALIBRATION_TOKENS = 2048


def init_weights(key, cfg, dtype):
    """The program's own draw (matrices and taps uniform in +-1/sqrt(fan
    in), the convolution's bias likewise, norm weights ones; ``A_log``,
    ``dt_bias`` and ``D`` as Mamba-2's initialiser: A in 1..16, dt in
    1e-3..1e-1 through the inverse softplus, D ones, so that a state is
    carried over hundreds of tokens), the routed experts of a layer related
    as ``glm4_moe_lite.init_weights`` relates them and for its reason
    (``EXPERT_OWN_SHARE``: the renormalised sigmoid scores of a token's 22
    picks are nearly equal, 5.5 of them land on this share, and a rounding
    that swaps the twenty-second for the twenty-third would otherwise
    replace its part by an unrelated function; the CPU tests hold single
    picks with unrelated experts), ``QK_GAIN``, and the routers'
    correction bias drawn as the load balance it is trained to be
    (``_balance_routers``)."""
    params = glm4_moe_lite.init_weights(key, cfg, dtype)
    for layer in params["layers"]:
        for name in ("q_proj", "k_proj"):
            if name in layer:
                w = layer[name]["w"]
                layer[name]["w"] = w * jnp.asarray(QK_GAIN, w.dtype)
    return _balance_routers(params, cfg, jax.random.fold_in(key, 46))


def _balance_routers(params, cfg, key):
    """``e_score_correction_bias`` of every router set so that each of its
    experts is picked about as often as the others, which is what training
    sets it to (the bias is the model's load balancer: it is added for the
    selection alone).  A random stack needs it more than a trained one.
    Its layers' outputs share one direction across all tokens (relu^2 and
    silu leave every unit a positive mean, which an out-projection turns
    into one vector; flat attention returns the context's mean), and by
    the fifth expert layer that direction is 53-58% of a router's input:
    the scores then differ more between experts than between tokens, every
    token picks nearly the same experts, a 128-token batch touches 54-62
    of the 128 held experts of that layer where a balanced router touches
    all, and the share of a token's 22 picks that land on this chip reads
    22.7-26.5% by the seed, which ``tok_s`` followed (5 047-5 253 over
    eight seeds; my chip runs, PR 46:
    ``scripts/nemotron_h_router_probe.py``).  Balanced, the share reads
    24.9-25.0% and a step touches 120 of the 128, which is the
    deployment's step; what it did not buy is a steadier ``tok_s``
    (PERF.md, Findings, PR 46).

    The balance: one sequence of ``CALIBRATION_TOKENS`` seeded ids goes
    through the layers in order, by the plain reference's own layer
    functions (float32), at each router the bias is set so that the picks
    over those tokens fall on every expert about equally often
    (``_balanced_bias``, which starts from the plain draw's +-0.05), and
    each layer runs with its new bias before the next is read.  The
    scores, and so the weights of the picks, are untouched, as in the
    model."""
    hp = (cfg.rms_eps, cfg.head_dim,
          (*cfg.mamba_dims, cfg.mamba_n_groups), cfg.num_experts_per_tok,
          float(cfg.routed_scaling_factor), cfg.expert_offset)
    ids = jax.random.randint(key, (CALIBRATION_TOKENS,), 3, cfg.vocab_size)
    # (at the platform's default matmul precision: a bias is being drawn,
    # and program and reference then read the same array)
    x = _f32(params["embed"]["w"][ids])
    for layer in params["layers"]:
        if "experts" in layer:
            h = _rms(x, _f32(layer["post_norm"]["w"]), cfg.rms_eps)
            scores = jax.nn.sigmoid(h @ _f32(layer["router"]["w"]))
            layer["router"] = dict(layer["router"], e_bias=_balanced_bias(
                scores, layer["router"]["e_bias"], cfg.num_experts_per_tok))
        x = _layer(layer, x, hp)
    return params


#: turns of the balancing rule below, its first step in units of the scores'
#: deviation, and the factor a turn's step shrinks by
BALANCE_TURNS, BALANCE_STEP, BALANCE_DECAY = 48, 0.3, 0.95


def _balanced_bias(scores, noise, top_k: int):
    """A correction bias under which the ``top_k`` largest of ``scores +
    bias`` ([tokens, experts]) fall on every expert about equally often.
    It starts where each expert clears a common threshold in k / E of the
    tokens (minus its own (1 - k / E) quantile) and is then moved as
    training moves it (the auxiliary-loss-free balancing rule): an expert
    picked more often than its share has its bias lowered, one picked less
    often raised, in proportion, a shrinking step a turn.  The quantile
    alone balances experts whose scores vary independently, and a stack's
    scores move together across experts.  ``noise`` (the plain draw's
    +-0.05) is where the rule starts from beside the quantile, not added
    after it: it is half a score's deviation over tokens, and on top of a
    balanced bias it unbalances it again (the quantile with the noise
    after it left a 128-token step touching 107 of 128 held experts; my
    chip runs, PR 46)."""
    tokens, width = scores.shape
    bar = jnp.quantile(scores, 1.0 - top_k / width, axis=0)
    spread = jnp.std(scores)

    def turn(i, bias):
        _, picks = jax.lax.top_k(scores + bias, top_k)
        load = jnp.zeros((width,), jnp.float32).at[picks.reshape(-1)].add(
            width / (tokens * top_k))            # 1.0: an expert's share
        return bias + BALANCE_STEP * BALANCE_DECAY ** i * spread * (1 - load)

    return jax.lax.fori_loop(0, BALANCE_TURNS, turn,
                             jnp.mean(bar) - bar + noise)


def param_specs(shapes):
    raise SystemExit("benchmark: the state cache of a model with Mamba-2 "
                     "layers has no tensor-parallel layout; "
                     "tensor_parallel_size is 1")


# -------------------------------------------------------------- reference
#: experts upcast to float32 at a time: bounds the transient to
#: 8 x 2 x 1024 x 2688 x 4 B = 0.18 GB of weights and
#: 8 x 2568 x (2688 + 1024) x 4 B = 0.31 GB of activations beside a full
#: chip
EXPERT_CHUNK = 8
#: query rows of the dense masked softmax computed at a time: 32 heads x
#: 512 x 2568 scores x 4 B = 0.17 GB
QUERY_BLOCK = 512
#: columns of the head taken at a time: 4096 x 8192 x 4 B = 0.13 GB in
#: float32, where the held slice is 0.54 GB
VOCAB_BLOCK = 8192


def _f32(a):
    return a.astype(jnp.float32)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _relu2(x):
    return jnp.square(jax.nn.relu(x))


def _experts(layer, h, top_k, scale, offset):
    """The ``E`` sublayer for normed rows h: the held experts' part of the
    routed sum in the latent (every held expert computed for every token
    and weighted by the router, zero where it did not pick the expert),
    brought back to the model's width, plus the shared expert on h."""
    t = h.shape[0]
    scores = jax.nn.sigmoid(h @ _f32(layer["router"]["w"]))
    _, top_i = jax.lax.top_k(scores + layer["router"]["e_bias"], top_k)
    top_s = jnp.take_along_axis(scores, top_i, axis=-1)
    combine = jnp.zeros_like(scores).at[jnp.arange(t)[:, None], top_i].set(
        scale * top_s / jnp.sum(top_s, -1, keepdims=True))
    held = layer["experts"]["up"].shape[0]
    combine = combine[:, offset:offset + held]
    latent = h @ _f32(layer["fc1_latent_proj"]["w"])
    r = jnp.zeros_like(latent)
    for e0 in range(0, held, EXPERT_CHUNK):
        sl = slice(e0, e0 + EXPERT_CHUNK)
        up = jnp.einsum("tl,elf->etf", latent, _f32(layer["experts"]["up"][sl]))
        out = jnp.einsum("etf,efl->etl", _relu2(up),
                         _f32(layer["experts"]["down"][sl]))
        r = r + jnp.einsum("etl,te->tl", out, combine[:, sl])
    shared = layer["shared_expert"]
    return r @ _f32(layer["fc2_latent_proj"]["w"]) \
        + _relu2(h @ _f32(shared["up"]["w"])) @ _f32(shared["down"]["w"])


def _mamba(layer, h, dims, eps):
    """The Mamba-2 mixer of normed rows h [T, hidden], one sequence from
    its first token, the recurrence token by token; ``dims`` = (heads, a
    head's width, the state's width, groups)."""
    n_h, d_h, n, groups = dims
    mb = layer["mamba"]
    t, d_inner, gn = h.shape[0], n_h * d_h, groups * n
    zxbcdt = h @ _f32(mb["in_proj"]["w"])
    z = zxbcdt[:, :d_inner]
    xbc = zxbcdt[:, d_inner:2 * d_inner + 2 * gn]
    dt = jax.nn.softplus(zxbcdt[:, 2 * d_inner + 2 * gn:]
                         + _f32(mb["dt_bias"]))               # [T, H]
    w = _f32(mb["conv"]["w"])                     # [K, C], w[K-1] on t
    kk = w.shape[0]
    padded = jnp.pad(xbc, ((kk - 1, 0), (0, 0)))  # zeros before token 0
    xbc = jax.nn.silu(sum(padded[j:j + t] * w[j] for j in range(kk))
                      + _f32(mb["conv"]["b"]))
    u = xbc[:, :d_inner].reshape(t, n_h, d_h)
    # a head reads the B and C of its group: heads / groups heads each
    b, c = (jnp.repeat(xbc[:, lo:lo + gn].reshape(t, groups, n),
                       n_h // groups, axis=1)
            for lo in (d_inner, d_inner + gn))    # [T, H, N]
    a = -jnp.exp(_f32(mb["A_log"])) * dt

    def token(state, xs):                         # state [H, P, N]
        u_t, dt_t, a_t, b_t, c_t = xs
        state = jnp.exp(a_t)[:, None, None] * state \
            + (dt_t[:, None] * u_t)[:, :, None] * b_t[:, None, :]
        return state, jnp.einsum("hpn,hn->hp", state, c_t)

    _, y = jax.lax.scan(token, jnp.zeros((n_h, d_h, n), jnp.float32),
                        (u, dt, a, b, c))
    y = y + _f32(mb["D"])[None, :, None] * u
    y = y.reshape(t, d_inner) * jax.nn.silu(z)
    # the gated norm, a group's channels at a time; the weight is every
    # channel's own
    y = y.reshape(t, groups, d_inner // groups)
    y = y * jax.lax.rsqrt(jnp.mean(y * y, -1, keepdims=True) + eps)
    return (y.reshape(t, d_inner) * _f32(mb["norm"]["w"])) \
        @ _f32(mb["out_proj"]["w"])


def _attention(layer, h, head_dim):
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
        s = jnp.einsum("thd,shd->hts", q[t0:t0 + QUERY_BLOCK], k) \
            * head_dim ** -0.5
        s = jnp.where(rows[None, :, None] >= pos[None, None, :], s, -jnp.inf)
        outs.append(jnp.einsum("hts,shd->thd", jax.nn.softmax(s, -1), v))
    o = jnp.concatenate(outs, axis=0)
    return o.reshape(t, -1) @ _f32(layer["o_proj"]["w"])


@functools.partial(jax.jit, static_argnames=("hp",))
def _layer(layer, x, hp):
    """One sublayer on x [T, hidden] float32; ``hp`` = (eps, head_dim,
    mamba dims with the groups, top_k, routed scaling, offset).  What a
    layer is, its weights say: one norm and one branch."""
    eps, head_dim, dims, top_k, scale, offset = hp
    if "experts" in layer:
        h = _rms(x, _f32(layer["post_norm"]["w"]), eps)
        return x + _experts(layer, h, top_k, scale, offset)
    h = _rms(x, _f32(layer["input_norm"]["w"]), eps)
    if "mamba" in layer:
        return x + _mamba(layer, h, dims, eps)
    return x + _attention(layer, h, head_dim)


@jax.jit
def _head_block(h, columns):
    return h @ _f32(columns)


def reference_logprobs(params, config: dict, token_ids: list[int],
                       last_n: int, tp: int = 1) -> jax.Array:
    """Log-softmax over the held vocabulary after the last ``last_n``
    positions of ``token_ids``: [last_n, vocab] float32.  ``params`` is the
    served weight tree (upcast here, layer by layer, the experts and the
    head in blocks); ``config`` holds the keys of the configuration file."""
    if tp != 1:
        raise SystemExit("benchmark: the Nemotron-H reference has no "
                         "tensor-parallel weight layout")
    eps = config["layer_norm_epsilon"]
    hp = (eps, config["head_dim"],
          (config["mamba_num_heads"], config["mamba_head_dim"],
           config["ssm_state_size"], config["n_groups"]),
          config["num_experts_per_tok"],
          float(config["routed_scaling_factor"]),
          config.get("expert_offset", 0))
    ids = jnp.asarray(token_ids, jnp.int32)
    head = params["lm_head"]["w"]                 # [hidden, vocab held]
    with jax.default_matmul_precision("highest"):
        x = _f32(params["embed"]["w"][ids])
        for layer in params["layers"]:
            x = _layer(layer, x, hp)
        h = _rms(x[-last_n:], _f32(params["final_norm"]["w"]), eps)
        logits = jnp.concatenate(
            [_head_block(h, head[:, v0:v0 + VOCAB_BLOCK])
             for v0 in range(0, head.shape[1], VOCAB_BLOCK)], axis=-1)
        return jax.nn.log_softmax(logits, -1)


# ----------------------------------------------------------------- counts
def model_costs(config: dict, dtype_bytes: int = 2) -> dict:
    """Operations and bytes the decoder's algorithm needs on this chip,
    from the sizes of the configuration file (``n_routed_experts`` and
    ``vocab_size`` held here, the router at its published width)."""
    h = config["hidden_size"]
    heads, d = config["num_attention_heads"], config["head_dim"]
    q_dim, kv_dim = heads * d, config["num_key_value_heads"] * d
    mix = _layer_mix(config)
    n_h, d_h = config["mamba_num_heads"], config["mamba_head_dim"]
    n, kk = config["ssm_state_size"], config["conv_kernel"]
    d_inner = n_h * d_h
    conv_dim = d_inner + 2 * config["n_groups"] * n
    held, width = config["n_routed_experts"], _router_width(config)
    top_k, latent = config["num_experts_per_tok"], config["moe_latent_size"]
    attn_params = h * q_dim + 2 * h * kv_dim + q_dim * h
    mamba_proj = h * (d_inner + conv_dim + n_h) + d_inner * h
    # the taps and their bias, A_log, dt_bias and D, the gated norm
    mamba_small = (kk + 1) * conv_dim + 3 * n_h + d_inner
    # read by every dispatch under omni.moe: the router (with its bias)
    # and the ungated shared expert; under omni.moe_latent: W_1 and W_2
    shared_params = 2 * h * config["moe_shared_expert_intermediate_size"]
    fixed_mlp = h * width + width + shared_params
    latent_params = 2 * h * latent
    expert_params = 2 * latent * config["moe_intermediate_size"]
    # of a token's top-k picks, those expected to land on a held expert
    picks_here = top_k * held / width
    # the rule, per token and head, by the recurrent algorithm: the decay
    # of S, the rank-one update (a product and a sum) and S C (a product
    # and a sum); around it the taps, the bias and silu, softplus and the
    # decay, the skip, the gate and the norm
    rule_flops = n_h * 5.0 * d_h * n
    ssd_flops = (2.0 * kk + 6.0) * conv_dim + rule_flops + 12.0 * d_inner
    moe_flops = 2.0 * (h * width + shared_params
                       + picks_here * expert_params)
    return {
        "mix": mix, "held": held, "width": width, "top_k": top_k,
        "q_dim": q_dim, "conv_dim": conv_dim, "d_inner": d_inner,
        "n_h": n_h, "latent": latent,
        # a layer's parameters with its one norm, as the issue counts them
        "mamba_params": mamba_proj + mamba_small + h,
        "attn_params": attn_params + h,
        "fixed_mlp_params": fixed_mlp + latent_params + h,
        "expert_params": expert_params,
        "flops_per_token": (
            2.0 * (mix["attention"] * attn_params
                   + mix["mamba"] * mamba_proj + mix["moe"] * latent_params)
            + mix["moe"] * moe_flops + mix["mamba"] * ssd_flops),
        "ssd_flops_per_token": ssd_flops,
        "moe_flops_per_token": moe_flops,
        "moe_latent_flops_per_token": 2.0 * latent_params,
        # a pair a query head: the score over D numbers and the value over
        # D, a product and a sum each
        "attn_flops_per_pair": 4.0 * q_dim * mix["attention"],
        "head_flops_per_row": 2.0 * h * config["vocab_size"],
        # every weight outside the experts' stacks, the head once (the
        # embedding's rows a step reads are its tokens': step_cost)
        "fixed_weight_bytes": dtype_bytes * (
            mix["attention"] * (attn_params + h)
            + mix["mamba"] * (mamba_proj + mamba_small + h)
            + mix["moe"] * (fixed_mlp + latent_params + h)
            + h * config["vocab_size"] + h),
        "fixed_mlp_bytes": dtype_bytes * fixed_mlp,
        "latent_bytes": dtype_bytes * latent_params,
        "expert_bytes": dtype_bytes * expert_params,
        "ssd_weight_bytes": dtype_bytes * mamba_small,
        "kv_bytes_per_pos": dtype_bytes * 2 * kv_dim * mix["attention"],
        # one sequence's state in one Mamba-2 layer, float32: the rule's
        # [heads, head_dim, state] and the convolution's K - 1 carried rows
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
              + dtype_bytes * tokens * config["hidden_size"]
              + mix["moe"] * _touched(costs, tokens) * costs["expert_bytes"]
              + costs["kv_bytes_per_pos"] * (kv_read + tokens)
              + mix["mamba"] * 2.0 * _state_rows(step)
              * (costs["state_bytes"] + costs["conv_state_bytes"]))
    return flops, nbytes


def attn_cost(step: dict, config: dict, dtype_bytes: int = 2
              ) -> tuple[float, float]:
    """The attention of one step, as ``qwen3_moe.attn_cost`` counts it,
    over the attention layer only: ``4 x 128 x ctx`` operations a query
    head, 1 024 B a cached token.  Bytes: every decode row reads its
    context once, a chunk reads what it attends once, and queries come in
    and outputs go out at the heads' width."""
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
    """What runs under ``omni.moe`` in one step: the router at its whole
    width, the picks that land on held experts AT THE LATENT'S WIDTH and
    the shared expert per token; the router's and the shared expert's
    weights and as many held experts as the step's tokens are expected to
    touch, read once a layer, the model's row in and out and the latent's
    in and out.  The two projections between them are not here: their
    scope is their own (``moe_latent_cost``)."""
    costs = model_costs(config, dtype_bytes)
    layers = costs["mix"]["moe"]
    tokens = step["prefill_tokens"] + step["decodes"]
    flops = layers * tokens * costs["moe_flops_per_token"]
    nbytes = layers * (
        _touched(costs, tokens) * costs["expert_bytes"]
        + costs["fixed_mlp_bytes"]
        + 2.0 * dtype_bytes * tokens * (config["hidden_size"]
                                        + costs["latent"]))
    return flops, nbytes


def moe_latent_cost(step: dict, config: dict, dtype_bytes: int = 2
                    ) -> tuple[float, float]:
    """What runs under ``omni.moe_latent`` in one step: ``h W_1`` before
    the dispatch and ``r W_2`` after the combine, every token through
    both; the two matrices read once a layer, and each token's rows in
    and out at both widths."""
    costs = model_costs(config, dtype_bytes)
    layers = costs["mix"]["moe"]
    tokens = step["prefill_tokens"] + step["decodes"]
    flops = layers * tokens * costs["moe_latent_flops_per_token"]
    nbytes = layers * (
        costs["latent_bytes"]
        + 2.0 * dtype_bytes * tokens * (config["hidden_size"]
                                        + costs["latent"]))
    return flops, nbytes


def ssd_cost(step: dict, config: dict, dtype_bytes: int = 2
             ) -> tuple[float, float]:
    """What runs under ``omni.ssd`` in one step, as
    ``granite_moe_hybrid.ssd_cost`` counts it, with B and C a group in the
    convolution's channels (10 240, not 8 448): per token the taps, the
    bias and the activations, and a head the decay of ``S``, the rank-one
    update and ``S C`` (the chunked form a prompt's rows run does more
    products than these; they are not counted), the skip, the gate and
    the norm.  Bytes: every touched sequence's state and carried
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
