"""MiMo-V2.5's language model (``model_type`` ``mimo_v2``), as a
configuration file names it: ``"arch": "benchmark.archs.mimo_v2"``.  It
keeps the contract of ``benchmark/archs/qwen3_moe.py`` (``program_config``,
``init_weights``, ``param_specs``, ``reference_logprobs``, ``step_cost``,
``attn_cost``, ``moe_cost``), ``attn_window_cost`` as
``benchmark/archs/cohere2_moe.py`` has it, and adds ``proj_cost`` for the
roofline of the projections (what runs under ``omni.proj``).

The model (x a row of ``hidden_size``; ``n(x, w) = x * rsqrt(mean(x^2) +
layernorm_epsilon) * w``).  ``x_0 = E[ids]``.  Every layer is pre-norm
with two residual sums: ``x <- x + attn(n(x, w_a)); x <- x + mlp(n(x,
w_m))``.  After the last layer ``n(x, w_f) W_head`` (the head is untied).
``hybrid_layer_pattern[i]`` 0 is a *full* layer, 1 a *sliding* one.

*Attention*, either kind: ``q = h W_q`` as ``num_attention_heads`` heads of
``head_dim`` (192), ``k = h W_k`` as ``Hkv`` heads of 192, ``v =
attention_value_scale * (h W_v)`` as ``Hkv`` heads of ``v_head_dim`` (128);
no bias; ``Hkv`` is ``num_key_value_heads`` (4) on a full layer and
``swa_num_key_value_heads`` (8) on a sliding one; query head ``j`` reads kv
head ``j // (heads / Hkv)``.  The first ``r = int(head_dim *
partial_rotary_factor)`` (64) dims of every q and k head are rotated at the
token's position, dim ``i`` paired with ``i + r/2``, ``theta_i =
base^(-2i/r)``, base ``rope_theta`` (1e7) on a full layer and
``swa_rope_theta`` (1e4) on a sliding one; the other dims are not rotated.
Scores ``s[t, j] = q_t . k_j / sqrt(head_dim)``; a full layer admits ``j <=
t``, a sliding one ``t - sliding_window < j <= t``.  A full layer's softmax
is plain.  A sliding layer's holds one learned logit ``b_h`` a query head in
its DENOMINATOR, with no value: ``p[t, j] = exp(s[t, j] - m) / (exp(b_h - m)
+ sum_j' exp(s[t, j'] - m))``, ``m = max(b_h, max_j s[t, j])``; ``o_t =
sum_j p[t, j] v_j``; a row's weights sum to less than one.  ``out =
concat_h(o) W_o``, ``W_o`` ``[heads * v_head_dim, hidden]``.

*MLP.*  Where ``moe_layer_freq[i]`` is 0 a dense SwiGLU of
``intermediate_size``.  Elsewhere ``s = sigmoid(h W_r)`` in float32 over the
router's published width; the ``num_experts_per_tok`` largest of ``s +
e_score_correction_bias`` kept, weights ``s_e / sum of the kept s``; ``mlp(h)
= sum over the kept e HELD HERE (ids expert_offset .. + n_routed_experts)
of w_e D_e(silu(G_e h) * U_e h)``; no shared expert, no scaling factor, no
group limit.

The reference is straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``: a dense masked softmax with
the sink written as above (a kv head's query heads and a block of query
rows at a time), every held expert computed for every token and weighted by
the router, the dense MLP a block of its columns at a time and the head a
block of the vocabulary at a time (so that the check's 4 288 tokens fit
beside a full chip), no cache, no kernel, no batching, no window in the
walk.  It shares only the names of the weight tree with the program (whose
modules are imported inside the builder functions alone); the sizes come
from the configuration file's keys.

Departures from the published model, each stated in the file too:

- the chip's share: the file's ``n_routed_experts`` counts the experts HELD
  here, its ``published.n_routed_experts`` is the router's width.  A pick
  that lives on another chip adds nothing, in the program and here alike,
  and that partial result goes on to the next layer (model-configs guide,
  section 4);
- the vision tower, the audio encoder and the multi-token-prediction
  layers are left out (the file's ``assumed.towers``).
"""

from __future__ import annotations

import dataclasses
import functools
import importlib
import math

import jax
import jax.numpy as jnp

from benchmark.archs import glm4_moe_lite
# the same SwiGLU, ``cohere2_moe.TOKEN_BLOCK`` rows at a time
from benchmark.archs.cohere2_moe import _f32, _swiglu
from benchmark.archs.dots3_note import _touched  # the same share, the same count

#: what the file must say for the program to be the model: each is a switch
#: the family has and this model leaves one way
BUILT = {"attention_bias": False, "add_full_attention_sink_bias": False,
         "add_swa_attention_sink_bias": True, "hidden_act": "silu",
         "n_group": 1, "topk_group": 1, "norm_topk_prob": True,
         "scoring_func": "sigmoid", "topk_method": "noaux_tc",
         "tie_word_embeddings": False, "n_shared_experts": None,
         "routed_scaling_factor": None, "hybrid_block_size": None}
#: widths the config states once a kind of layer, which the program holds
#: once a model: the two must be equal
SAME = (("swa_head_dim", "head_dim"), ("swa_v_head_dim", "v_head_dim"),
        ("swa_num_attention_heads", "num_attention_heads"),
        ("sliding_window_size", "sliding_window"))
KIND = {0: "full_attention", 1: "sliding_attention"}


def _router_width(config: dict) -> int:
    return (config.get("published") or {}).get("n_routed_experts",
                                                config["n_routed_experts"])


def _kinds(config: dict) -> tuple[str, ...]:
    return tuple(KIND[p] for p in config["hybrid_layer_pattern"])


def _dense_layers(config: dict) -> int:
    """Leading layers whose MLP is dense (``moe_layer_freq`` 0); every
    layer after them is sparse, or the file is refused."""
    freq = list(config["moe_layer_freq"])
    dense = freq.index(1) if 1 in freq else len(freq)
    if any(f != 1 for f in freq[dense:]):
        raise SystemExit(f"benchmark: moe_layer_freq {freq} is not dense "
                         "layers and then sparse ones")
    return dense


def _layer_mix(config: dict) -> dict:
    """{sliding, full, dense, sparse: layers} among the file's
    ``num_hidden_layers``, in the mix its ``stage_layers`` state.  At the
    file's own depth these are its layers; a model twice as deep has twice
    each, so that every count is linear in depth, as the harness's
    contract has it."""
    stage = config["stage_layers"]
    share = config["num_hidden_layers"] / (stage["sliding"] + stage["full"])
    return {k: v * share for k, v in stage.items()}


# ---------------------------------------------------------------- builder
def program_config(config: dict):
    """The published keys of a configuration file -> the program's
    ``TransformerConfig``, with the expert share the file states.  Where
    the file names the program's own constant for this model
    (``program_config``), the two must agree on everything but depth, the
    pattern's cut and the share: a width that drifted in the program is an
    error here."""
    from vllm_omni_tpu.models.common.transformer import (
        HeadKind,
        TransformerConfig,
    )
    from vllm_omni_tpu.models.mimo_v2 import expert_share

    for key, want in BUILT.items():
        if config[key] != want:
            raise SystemExit(f"benchmark: {key} {config[key]!r} is not "
                             f"built (only {want!r})")
    for key, as_ in SAME:
        if config[key] != config[as_]:
            raise SystemExit(f"benchmark: {key} {config[key]!r} differs "
                             f"from {as_} {config[as_]!r}: not built")
    if config["rope_scaling"].get("rope_type", "default") != "default":
        raise SystemExit("benchmark: rope_scaling other than default is "
                         "not built")
    layers = config["num_hidden_layers"]
    kinds = _kinds(config)
    if len(kinds) != layers or len(config["moe_layer_freq"]) != layers:
        raise SystemExit(f"benchmark: hybrid_layer_pattern and "
                         f"moe_layer_freq are not {layers} layers")
    dense = _dense_layers(config)
    cfg = expert_share(TransformerConfig(
        vocab_size=config["vocab_size"],
        hidden_size=config["hidden_size"],
        num_layers=layers,
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"],
        v_head_dim=config["v_head_dim"],
        intermediate_size=config["intermediate_size"],
        rope_theta=float(config["rope_theta"]),
        rms_eps=config["layernorm_epsilon"],
        partial_rotary_factor=config["partial_rotary_factor"],
        attention_value_scale=config["attention_value_scale"],
        moe=True,
        num_experts=_router_width(config),
        num_experts_per_tok=config["num_experts_per_tok"],
        moe_intermediate_size=config["moe_intermediate_size"],
        moe_renormalize=True,
        first_k_dense_replace=dense,
        router_scoring="sigmoid",
        router_bias=True,
        layer_kinds=kinds,
        sliding_window=config["sliding_window"],
        sliding_heads=HeadKind(
            num_kv_heads=config["swa_num_key_value_heads"],
            rope_theta=float(config["swa_rope_theta"]), sink=True),
    ), held=config["n_routed_experts"], offset=config.get("expert_offset", 0))
    stage = config["stage_layers"]
    served = {"sliding": kinds.count("sliding_attention"),
              "full": kinds.count("full_attention"),
              "dense": dense, "sparse": layers - dense}
    if served != stage:
        raise SystemExit(f"benchmark: stage_layers {stage} are not the "
                         f"{layers} layers served, {served}")
    if config.get("program_config"):
        module, _, name = config["program_config"].partition(":")
        theirs = getattr(importlib.import_module(module), name)
        theirs = expert_share(dataclasses.replace(
            theirs, num_layers=layers, layer_kinds=kinds),
            held=cfg.num_experts, offset=cfg.expert_offset)
        if theirs != cfg:
            raise SystemExit(
                f"benchmark: {config['program_config']} differs from the "
                f"published configuration: {theirs} != {cfg}")
    return cfg


#: THE DRAW (``init_weights``): what is done to the program's own
#: initialiser (matrices uniform in +-1/sqrt(fan in), norm weights ones,
#: the embedding normal at 0.02, the routers' correction bias uniform in
#: +-0.05) so that the check can see what it must, and why.  The check
#: (``serve.reference_check``) reads the LARGEST of
#: ``reference_check.new_tokens`` greedy tokens' served logprobs against
#: the float32 reference, under one limit for every cell
#: (``serve.LOGPROB_TOL``, 0.15).  It has to read under the limit for every
#: sound run and over it with the sink, the window or the pages' precision
#: taken out of the served path (``scripts/sparse_window_control.py``).
#: The constants are ``cohere2_moe``'s where the reason is the same, and
#: that module has the readings that decided them (PERF.md, PR 50):
#:
#: - *a flat softmax.*  Under the plain draw a score deviates by a third: a
#:   softmax over 128 keys or over 4 000 is their average, next to nothing
#:   whatever the keys were.  ``QK_GAIN``: ``q_proj`` and ``k_proj`` times
#:   ``g`` each, a score then deviates by ``g ** 2 / 3``, by the layer's
#:   place in the file's seven.  4 on the first layer, whose operands are
#:   exact but for their own rounding, so that the pages' precision is the
#:   largest share of a sound run's error that it can be (at 3 there,
#:   float8 operands read 0.136-0.234 beside sound runs of 0.040-0.069: one
#:   seed of three under the limit; at 4, 0.42-0.60 beside 0.056-0.099;
#:   calls 197 and 198, the head at 4); 2 on the five sliding layers (sharp
#:   enough that WHICH 128 keys a row reads decides its output, flat enough
#:   not to compound every rounding); 3 on the last full layer;
#: - *a sink that weighs.*  The initialiser draws a sink in +-1, which
#:   beside 128 keys whose ``exp(s)`` sum to some 300 is a thousandth of a
#:   row's mass: leaving it out would move nothing.  A trained sink takes
#:   a fair part of a row's mass (that is what it is for).  ``SINK_MASS``:
#:   ``b_h = log(window) + sigma^2 / 2 + logit(mass_h)``, ``sigma`` the
#:   layer's score deviation (so that ``log(window) + sigma^2 / 2`` is the
#:   log of the expected sum over a full window), ``mass_h`` uniform in
#:   0.1-0.5 a head: the share of the row's softmax mass the sink holds at
#:   a full window;
#: - *a pick swapped between a held and an absent expert*:
#:   ``ROUTED_DOWN_GAIN``, the routed experts' ``down`` times 1/16
#:   (``cohere2_moe`` has the arithmetic).  Here there is no shared expert
#:   beside them, so what the chip's check sees of the six sparse MLPs is a
#:   sixteenth their size; it sees the dense MLP of layer 0 whole, and the
#:   CPU tests hold single picks, the correction bias and the share's
#:   offset with unrelated experts at full size
#:   (tests/engine/test_mimo_v2.py);
#: - *the largest of 64*: the file's ``reference_check.new_tokens``
#:   (``cohere2_moe``: a token's error under float8 operands is
#:   heavy-tailed, a sound token's is bounded by its roundings).
#:
#: What the chip read is in PERF.md (Findings, PR 55) and in the file's
#: ``assumed.weights``.
QK_GAIN = (4.0, 2.0, 2.0, 2.0, 2.0, 2.0, 3.0)
SINK_MASS = (0.1, 0.5)
ROUTED_DOWN_GAIN = 0.0625
#: - *logits that say little.*  The head is untied and drawn in
#:   +-1/sqrt(hidden): a logit deviates by 0.58 over the vocabulary, the
#:   greedy token's is 2.6, and everything the stream carries moves a
#:   logprob by little.  At that draw (call 196, the largest of 64 tokens
#:   after 4 224) a sound run read 0.0103-0.0151 over five seeds, of which
#:   half a bfloat16 step of the logit (0.0078) is most; no window 0.315
#:   and 0.710; **no sink 0.090 and 0.169, unscaled values 0.124 and
#:   0.098, float8 operands 0.057 and 0.058: three controls under the
#:   limit**.  ``HEAD_GAIN``: the head times 3, so that a logit deviates
#:   by 1.7, as a trained head's do, and every reading is three times what
#:   it was but for the logit's half step (0.016 at a greedy logit of 4-8,
#:   0.031 at 8-16).  At 4, 5 and 6 (calls 197 and 198) sound runs read to
#:   0.099, 0.092 and 0.096: the half step of a logit past 8 is a third of
#:   that and separates nothing.
HEAD_GAIN = 3.0
#: - *a stream that forgot its tokens.*  The embedding is drawn at 0.02 and
#:   a branch adds 0.2: after the first layer a row of the stream says next
#:   to nothing of its own token, the cell's byte prompts (95 distinct
#:   tokens) feed every token of a sequence nearly one vector, and all of
#:   them pick the same experts: the rows this chip's experts got of a
#:   chunk followed the seed and the sequence (the experts' ``ragged-dot``
#:   0.355-0.453 s of four seeds' 3 s traces, ``tok_s`` 17 746-18 434 over
#:   six seeds: a quartile spread of 3.2% where a new cell is admitted
#:   under 2%, with same-seed runs 0.04-0.08% apart; call 197).
#:   ``EMBED_GAIN``: the embedding times 16 (0.32 a number, what a branch
#:   adds), so that a token's row stays its own through the stack and a
#:   chunk's tokens pick over all families: ``ragged-dot`` 0.460-0.494 s and
#:   ``tok_s`` 17 392-17 689 over five seeds, a quartile spread of 1.0%
#:   (call 208).  A trained model's stream keeps its token too.
EMBED_GAIN = 16.0
#: The routers' columns are drawn in families, as ``cohere2_moe``'s are and
#: for its reason (the cell's byte prompts through a random stack feed every
#: token of a sequence nearly one vector, so that under the plain draw the
#: share of the picks that lands here follows the seed and the sequence):
#: expert ``e`` belongs to family ``e mod (width / ROUTER_FAMILY)`` (16
#: families of 16 at the published 256), its column ``sqrt(1 - a^2)`` its
#: family's direction and ``a = ROUTER_OWN_SHARE`` its own draw.  A token's
#: eight picks are eight of the sixteen members of its best family, and the
#: 16 experts held here (ids 0-15) are ONE OF EACH family: a token lands
#: one pick here with probability a half, which is this chip's share of a
#: balanced deployment (8 picks x 16 / 256).  AN ASSUMPTION (the file's
#: ``assumed.weights``): a deployment balances its chips' loads by where it
#: places the experts.  Half a pick is no whole number, so unlike Command
#: A+'s one pick a token this load is fixed in the mean of a chunk's tokens
#: and not in every token: ``EMBED_GAIN`` is what makes that mean steady.
ROUTER_FAMILY = 16
ROUTER_OWN_SHARE = 0.25


def init_weights(key, cfg, dtype):
    """The served tree: the program's own draw, the routed experts of a
    layer related as ``glm4_moe_lite.init_weights`` relates them and for
    its reason, and their ``down`` times ``ROUTED_DOWN_GAIN``; q and k
    times ``QK_GAIN``; the sliding layers' sinks at ``SINK_MASS``; the
    head times ``HEAD_GAIN`` and the embedding times ``EMBED_GAIN``; the
    routers' columns in families (``ROUTER_FAMILY``)."""
    params = glm4_moe_lite.init_weights(key, cfg, dtype)
    width = cfg.router_experts or cfg.num_experts
    families = width // ROUTER_FAMILY
    params["lm_head"]["w"] = params["lm_head"]["w"] * jnp.asarray(
        HEAD_GAIN, dtype)
    params["embed"]["w"] = params["embed"]["w"] * jnp.asarray(
        EMBED_GAIN, dtype)
    for i, layer in enumerate(params["layers"]):
        gain = QK_GAIN[i % len(QK_GAIN)]
        for name in ("q_proj", "k_proj"):
            layer[name]["w"] = layer[name]["w"] * jnp.asarray(gain, dtype)
        if "sinks" in layer:
            sigma = gain * gain / 3.0
            mass = jax.random.uniform(
                jax.random.fold_in(key, 80 + i), layer["sinks"].shape,
                jnp.float32, minval=SINK_MASS[0], maxval=SINK_MASS[1])
            layer["sinks"] = (math.log(cfg.sliding_window)
                              + sigma * sigma / 2.0
                              + jnp.log(mass / (1.0 - mass)))
        if "experts" not in layer:
            continue
        layer["experts"]["down"] = layer["experts"]["down"] * jnp.asarray(
            ROUTED_DOWN_GAIN, dtype)
        if families > 1 and width % ROUTER_FAMILY == 0:
            # expert e's column is mostly its family's (e mod families),
            # a draw of its own as the columns' are
            w = _f32(layer["router"]["w"])
            own = ROUTER_OWN_SHARE
            bound = cfg.hidden_size ** -0.5
            kin = jax.random.uniform(
                jax.random.fold_in(key, 50 + i), (w.shape[0], families),
                jnp.float32, minval=-bound, maxval=bound)
            layer["router"]["w"] = (
                (1.0 - own * own) ** 0.5 * jnp.tile(kin, (1, ROUTER_FAMILY))
                + own * w).astype(dtype)
    return params


def param_specs(shapes):
    raise SystemExit("benchmark: the window kind's pool has no "
                     "tensor-parallel layout; tensor_parallel_size is 1")


# -------------------------------------------------------------- reference
#: columns of the dense MLP upcast and computed at a time: 4096 of 16 384
#: are 3 x 4096 x 4096 x 4 B = 0.2 GB in float32, where the whole is 0.8 GB
DENSE_BLOCK = 4096
#: query rows of the dense masked softmax computed at a time, for the 8 or
#: 16 query heads of one kv head: 16 x 256 x 4288 scores x 4 B = 70 MB
QUERY_BLOCK = 256
#: columns of the head taken at a time: 4096 x 8192 x 4 B = 0.13 GB in
#: float32, where the whole is 2.5 GB
VOCAB_BLOCK = 8192


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, pos, theta, rotary):
    """The published rotation of x [T, H, D] at positions ``pos``: the
    first ``rotary`` dims of a head, dim i paired with i + rotary / 2; the
    others as they are."""
    half = rotary // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[:, None] * inv[None, :]
    c, s = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    # the head in blocks of ``half`` dims, put together again by stack and
    # reshape (a ``concatenate`` of pieces narrower than a lane tile on the
    # last axis aborts the XLA TPU compiler: ``ops/rope.py``)
    b = x.reshape(*x.shape[:-1], -1, half)
    x1, x2 = b[..., 0, :], b[..., 1, :]
    return jnp.stack(
        [x1 * c - x2 * s, x2 * c + x1 * s]
        + [b[..., i, :] for i in range(2, b.shape[-2])],
        axis=-2).reshape(x.shape)


def _attention(layer, h, hp):
    """Grouped-query attention of normed rows h [T, hidden], one sequence:
    a dense masked softmax, one kv head's query heads and ``QUERY_BLOCK``
    query rows at a time.  ``hp`` = (head_dim, v_head_dim, kv heads, value
    scale, rotary dims, theta, window or 0); the layer has a sink iff its
    weights hold ``sinks``."""
    d, dv, kv_heads, v_scale, rotary, theta, window = hp
    t = h.shape[0]
    pos = jnp.arange(t)
    k = _rope((h @ _f32(layer["k_proj"]["w"])).reshape(t, kv_heads, d),
              pos, theta, rotary)
    v = v_scale * (h @ _f32(layer["v_proj"]["w"])).reshape(t, kv_heads, dv)
    w_q = _f32(layer["q_proj"]["w"])
    cols = w_q.shape[1] // kv_heads
    group = cols // d
    outs = []
    for g in range(kv_heads):
        q = _rope((h @ w_q[:, g * cols:(g + 1) * cols]).reshape(t, group, d),
                  pos, theta, rotary)
        k_g, v_g = k[:, g], v[:, g]
        sink = (_f32(layer["sinks"])[g * group:(g + 1) * group]
                if "sinks" in layer else None)

        def rows(block, k_g=k_g, v_g=v_g, sink=sink):
            q_b, pos_b = block
            s = jnp.einsum("thd,sd->hts", q_b, k_g) / jnp.sqrt(float(d))
            ok = pos_b[:, None] >= pos[None, :]
            if window:
                ok &= pos[None, :] > pos_b[:, None] - window
            s = jnp.where(ok[None], s, -jnp.inf)
            if sink is None:
                p = jax.nn.softmax(s, -1)
            else:
                # one more logit a head in the denominator, with no value
                b = sink[:, None, None]
                m = jnp.maximum(jnp.max(s, -1, keepdims=True), b)
                e = jnp.exp(s - m)
                p = e / (jnp.exp(b - m) + jnp.sum(e, -1, keepdims=True))
            return jnp.einsum("hts,sd->thd", p, v_g)

        pad = -t % QUERY_BLOCK
        q_p = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(
            -1, QUERY_BLOCK, *q.shape[1:])
        # a padding row sits past the sequence and sees every key
        pos_p = jnp.arange(t + pad).reshape(-1, QUERY_BLOCK)
        o = jax.lax.map(rows, (q_p, pos_p))
        outs.append(o.reshape(-1, *o.shape[2:])[:t])
    o = jnp.concatenate(outs, axis=1)                  # [T, H, Dv]
    return o.reshape(t, -1) @ _f32(layer["o_proj"]["w"])


def _dense_mlp(layer, h):
    """The dense SwiGLU, ``DENSE_BLOCK`` of its columns at a time (the
    products over disjoint columns add up to the whole)."""
    gate_up, down = layer["gate_up"]["w"], layer["down"]["w"]
    inter = down.shape[0]
    y = jnp.zeros_like(h)
    for c in range(0, inter, DENSE_BLOCK):
        sl = slice(c, min(c + DENSE_BLOCK, inter))
        y = y + _swiglu(h, _f32(gate_up[:, sl]),
                        _f32(gate_up[:, inter + sl.start:inter + sl.stop]),
                        _f32(down[sl]))
    return y


def _moe_mlp(layer, h, top_k, offset):
    """The held experts' part of the routed sum for normed rows h: sigmoid
    scores, the ``top_k`` largest of score + correction bias kept and
    weighted by their renormalised SCORES, every held expert computed for
    every token and weighted by that (zero where it was not picked)."""
    t = h.shape[0]
    scores = jax.nn.sigmoid(h @ _f32(layer["router"]["w"]))
    _, top_i = jax.lax.top_k(scores + layer["router"]["e_bias"], top_k)
    top_s = jnp.take_along_axis(scores, top_i, axis=-1)
    combine = jnp.zeros_like(scores).at[jnp.arange(t)[:, None], top_i].set(
        top_s / jnp.sum(top_s, -1, keepdims=True))
    experts = layer["experts"]
    held, inter = experts["down"].shape[:2]
    combine = combine[:, offset:offset + held]

    def add_expert(y, xs):
        gate_up, down, w = xs
        out = _swiglu(h, _f32(gate_up[:, :inter]), _f32(gate_up[:, inter:]),
                      _f32(down))
        return y + out * w[:, None], None

    y, _ = jax.lax.scan(add_expert, jnp.zeros_like(h),
                        (experts["gate_up"], experts["down"], combine.T))
    return y


@functools.partial(jax.jit, static_argnames=("hp",))
def _layer(layer, x, hp):
    """One layer on x [T, hidden] float32; ``hp`` = (eps, top_k, offset,
    ``_attention``'s hp)."""
    eps, top_k, offset, attn_hp = hp
    x = x + _attention(layer, _rms(x, _f32(layer["input_norm"]["w"]), eps),
                       attn_hp)
    h = _rms(x, _f32(layer["post_norm"]["w"]), eps)
    if "experts" in layer:
        return x + _moe_mlp(layer, h, top_k, offset)
    return x + _dense_mlp(layer, h)


@functools.partial(jax.jit, static_argnames=("block",))
def _head_block(h, w, start, block):
    return h @ _f32(jax.lax.dynamic_slice_in_dim(w, start, block, axis=1))


def _head(h, w):
    """Logits of rows h over the vocabulary, ``VOCAB_BLOCK`` columns of the
    head ``w [hidden, vocab]`` at a time (the last block starts early and
    its overlap is dropped)."""
    vocab = w.shape[1]
    block = min(VOCAB_BLOCK, vocab)
    parts = []
    for lo in range(0, vocab, block):
        start = min(lo, vocab - block)
        parts.append(_head_block(h, w, start, block)[:, lo - start:])
    return jnp.concatenate(parts, axis=1)


def _layer_hps(config: dict) -> dict:
    """{layer kind: ``_layer``'s ``hp``} from the file's keys."""
    d = config["head_dim"]
    rotary = int(d * config["partial_rotary_factor"])
    head = (config["layernorm_epsilon"], config["num_experts_per_tok"],
            config.get("expert_offset", 0))
    widths = (d, config["v_head_dim"])
    tail = (float(config["attention_value_scale"]), rotary)
    return {
        "sliding_attention": head + (
            widths + (config["swa_num_key_value_heads"],) + tail
            + (float(config["swa_rope_theta"]), config["sliding_window"]),),
        "full_attention": head + (
            widths + (config["num_key_value_heads"],) + tail
            + (float(config["rope_theta"]), 0),)}


def reference_logprobs(params, config: dict, token_ids: list[int],
                       last_n: int, tp: int = 1) -> jax.Array:
    """Log-softmax over the vocabulary after the last ``last_n`` positions
    of ``token_ids``: [last_n, vocab] float32.  ``params`` is the served
    weight tree (upcast here, layer by layer, the MLPs and the head in
    blocks); ``config`` holds the keys of the configuration file."""
    if tp != 1:
        raise SystemExit("benchmark: the MiMo-V2 reference has no "
                         "tensor-parallel weight layout")
    hps = _layer_hps(config)
    ids = jnp.asarray(token_ids, jnp.int32)
    with jax.default_matmul_precision("highest"):
        x = _f32(params["embed"]["w"][ids])
        for layer, kind in zip(params["layers"], _kinds(config)):
            x = _layer(layer, x, hps[kind])
        h = _rms(x[-last_n:], _f32(params["final_norm"]["w"]),
                 config["layernorm_epsilon"])
        return jax.nn.log_softmax(_head(h, params["lm_head"]["w"]), -1)


# ----------------------------------------------------------------- counts
def model_costs(config: dict, dtype_bytes: int = 2) -> dict:
    """Operations and bytes the decoder's algorithm needs on this chip,
    from the sizes of the configuration file (``n_routed_experts`` held
    here, the router at its published width).  Bytes are those the
    algorithm USES: a key of ``head_dim`` numbers and a value of
    ``v_head_dim``, whatever lanes the served pools pad them to."""
    h = config["hidden_size"]
    d, dv = config["head_dim"], config["v_head_dim"]
    heads = config["num_attention_heads"]
    mix = _layer_mix(config)
    layers = mix["sliding"] + mix["full"]
    kv_heads = {"sliding": config["swa_num_key_value_heads"],
                "full": config["num_key_value_heads"]}
    held, width = config["n_routed_experts"], _router_width(config)
    top_k = config["num_experts_per_tok"]
    # q, k, v and o of a layer of each kind
    attn_params = {kind: h * heads * d + h * n * (d + dv) + heads * dv * h
                   for kind, n in kv_heads.items()}
    proj_params = sum(mix[kind] * attn_params[kind] for kind in kv_heads)
    expert_params = 3 * h * config["moe_intermediate_size"]
    dense_params = 3 * h * config["intermediate_size"]
    router_params = h * width
    # of a token's top-k picks, those expected to land on a held expert
    picks_here = top_k * held / width
    return {
        "mix": mix, "layers": layers, "held": held, "width": width,
        "top_k": top_k, "kv_heads": kv_heads,
        "proj_params": proj_params, "expert_params": expert_params,
        "dense_params": dense_params, "router_params": router_params,
        "flops_per_token": 2.0 * (
            proj_params + mix["dense"] * dense_params
            + mix["sparse"] * (router_params + picks_here * expert_params)),
        "moe_flops_per_token": 2.0 * (router_params
                                      + picks_here * expert_params),
        # a pair a query head, a layer: the score over ``head_dim``
        # numbers and the value over ``v_head_dim``, a product and a sum
        "attn_flops_per_pair": 2.0 * heads * (d + dv),
        "head_flops_per_row": 2.0 * h * config["vocab_size"],
        # the embedding's rows are gathered, the head is read whole
        "fixed_weight_bytes": dtype_bytes * (
            proj_params + mix["dense"] * dense_params
            + mix["sparse"] * router_params + h * config["vocab_size"]),
        "router_bytes": dtype_bytes * router_params,
        "expert_bytes": dtype_bytes * expert_params,
        # a cached token in ONE layer of a kind: a key and a value of
        # every kv head of the kind
        "kv_bytes_per_pos": {kind: dtype_bytes * n * (d + dv)
                             for kind, n in kv_heads.items()},
        # a token's queries in and outputs out, a layer
        "q_io_bytes": dtype_bytes * heads * (d + dv),
    }


def _attention_cost(step: dict, costs: dict, kind: str, bound: str,
                    cap: int) -> tuple[float, float]:
    """One kind's attention over the pairs its bound leaves (``window``:
    the flight record's ``<phase>_ctx_window``, ``LLMEngine._record_step``;
    a record without them, or no bound, counts every visible key): ``2 x
    (192 + 128)`` operations a pair a query head.  Bytes: a decode row
    reads the rows it attends once, a chunk those its bound can reach once,
    and queries come in and outputs go out at the heads' widths."""
    prefill = step.get(f"prefill_ctx_{bound}", step["prefill_ctx"])
    decode = step.get(f"decode_ctx_{bound}", step["decode_ctx"])
    tokens = step["prefill_tokens"] + step["decodes"]
    kv_read = decode
    if step["prefill_tokens"] > 0:
        reach = (step["prefills"] * step["prefill_ctx"]
                 / step["prefill_tokens"] + step["prefill_tokens"] / 2.0)
        if cap:
            reach = min(reach, step["prefills"] * cap
                        + step["prefill_tokens"])
        kv_read += reach
    layers = costs["mix"][kind]
    flops = layers * costs["attn_flops_per_pair"] * (prefill + decode)
    nbytes = layers * (costs["kv_bytes_per_pos"][kind] * kv_read
                       + tokens * costs["q_io_bytes"])
    return flops, nbytes


def attn_cost(step: dict, config: dict, dtype_bytes: int = 2
              ) -> tuple[float, float]:
    """The full layers' attention (what runs under ``omni.attn``): every
    key at or below the token, 4 kv heads x (192 + 128) numbers a cached
    token a layer."""
    return _attention_cost(step, model_costs(config, dtype_bytes), "full",
                           "full", 0)


def attn_window_cost(step: dict, config: dict, dtype_bytes: int = 2
                     ) -> tuple[float, float]:
    """The sliding layers' attention (``omni.attn_window``):
    ``min(ctx, sliding_window)`` keys a token, 8 kv heads x (192 + 128)
    numbers a cached token a layer."""
    return _attention_cost(step, model_costs(config, dtype_bytes), "sliding",
                           "window", config["sliding_window"])


def moe_cost(step: dict, config: dict, dtype_bytes: int = 2
             ) -> tuple[float, float]:
    """The sparse MLPs of one step (what runs under ``omni.moe``): the
    router at its whole width and the picks that land on held experts per
    token; the router's weights and as many held experts as the step's
    tokens are expected to touch, read once a sparse layer, and the
    activations in and out."""
    costs = model_costs(config, dtype_bytes)
    tokens = step["prefill_tokens"] + step["decodes"]
    sparse = costs["mix"]["sparse"]
    flops = sparse * tokens * costs["moe_flops_per_token"]
    nbytes = sparse * (
        _touched(costs, tokens) * costs["expert_bytes"]
        + costs["router_bytes"]
        + 2.0 * dtype_bytes * tokens * config["hidden_size"])
    return flops, nbytes


def proj_cost(step: dict, config: dict, dtype_bytes: int = 2
              ) -> tuple[float, float]:
    """The attention layers' projections of one step (the matrix products
    under ``omni.proj``: q, k, v and o of every layer; the norms, the
    rotation and the residual sums that share the scope are counted as
    the bytes of the rows they read and write): every projection's weights
    read once, a token's row in and out and its q, k, v and o once."""
    costs = model_costs(config, dtype_bytes)
    tokens = step["prefill_tokens"] + step["decodes"]
    if tokens <= 0:
        return None
    flops = 2.0 * tokens * costs["proj_params"]
    row = 2.0 * config["hidden_size"] + (
        costs["q_io_bytes"] / dtype_bytes
        + sum(costs["mix"][kind] * costs["kv_bytes_per_pos"][kind]
              for kind in costs["kv_heads"]) / dtype_bytes / costs["layers"])
    nbytes = dtype_bytes * (costs["proj_params"]
                            + costs["layers"] * tokens * row)
    return flops, nbytes


def step_cost(step: dict, config: dict, mean_prefill_ctx: float,
              mean_decode_ctx: float, dtype_bytes: int = 2
              ) -> tuple[float, float] | None:
    """One whole step (``prefill_tokens``, ``decodes``, ``prefills``), as
    ``qwen3_moe.step_cost`` counts it: every weight it touches read once
    (of the held experts as many as its tokens are expected to route to),
    the attention of both kinds over the keys their bounds leave, and the
    rows written.  ``mean_*_ctx`` stand in where the record holds no pairs
    of its own (a mix's means: the window then caps the means)."""
    costs = model_costs(config, dtype_bytes)
    tokens = step["prefill_tokens"] + step["decodes"]
    if tokens <= 0:
        return None
    rec = {"prefill_ctx": step["prefill_tokens"] * mean_prefill_ctx,
           "decode_ctx": step["decodes"] * mean_decode_ctx, **step}
    if "prefill_ctx_window" not in rec:
        cap = config["sliding_window"]
        rec["prefill_ctx_window"] = step["prefill_tokens"] * min(
            mean_prefill_ctx, cap)
        rec["decode_ctx_window"] = step["decodes"] * min(mean_decode_ctx,
                                                         cap)
    parts = [attn_cost(rec, config, dtype_bytes),
             attn_window_cost(rec, config, dtype_bytes)]
    written = sum(costs["mix"][kind] * costs["kv_bytes_per_pos"][kind]
                  for kind in costs["kv_heads"])
    flops = (costs["flops_per_token"] * tokens
             + costs["head_flops_per_row"] * (step["prefills"]
                                              + step["decodes"])
             + sum(p[0] for p in parts))
    nbytes = (costs["fixed_weight_bytes"]
              + costs["mix"]["sparse"] * _touched(costs, tokens)
              * costs["expert_bytes"]
              + sum(p[1] for p in parts) + written * tokens)
    return flops, nbytes
