"""The command-a-plus-05-2026 decoder (``model_type`` ``cohere2_moe``), as a
configuration file names it: ``"arch": "benchmark.archs.cohere2_moe"``.  It
keeps the contract of ``benchmark/archs/qwen3_moe.py`` (``program_config``,
``init_weights``, ``param_specs``, ``reference_logprobs``, ``step_cost``,
``attn_cost``, ``moe_cost``) and adds ``attn_window_cost`` for the roofline
of the sliding layers' attention.

The model (x a row of ``hidden_size``; ``LN(x, w) = (x - mean(x)) *
rsqrt(var(x) + layer_norm_eps) * w``, a weight and no bias, mean and
variance over the row).  ``x_0 = E[ids]``.  Every layer is the PARALLEL
block ``h = LN(x, w_l); x <- x + attn(h) + moe(h)``: one norm a layer, both
branches read it.  After the last layer ``LN(x, w_f) E^T * logit_scale``
(the head is the embedding).

*Attention.*  ``q = h W_q`` as ``num_attention_heads`` heads of
``head_dim``, ``k = h W_k`` and ``v = h W_v`` as ``num_key_value_heads``;
no bias, no q/k norm; query head ``j`` reads kv head ``j // (heads / kv
heads)``; softmax of ``q . k / sqrt(head_dim)``; ``W_o``.  A *sliding*
layer (``layer_types``) rotates q and k at the token's position over the
whole head, NEIGHBOURING dims paired (``rope_gptj``): ``(y[2i], y[2i+1]) =
(x[2i] c_i - x[2i+1] s_i, x[2i+1] c_i + x[2i] s_i)``, ``theta_i =
rope_theta^(-2i / head_dim)``, and query ``t`` attends key ``s`` iff ``t -
sliding_window < s <= t``.  A *full* layer rotates nothing and attends
every ``s <= t``.

*MLP.*  ``s = sigmoid(h W_r)`` in float32 over the router's published
width; the ``num_experts_per_tok`` largest kept, weights ``s_e / sum of the
kept s``; ``r = sum over the kept e HELD HERE (ids expert_offset .. +
num_experts) of w_e D_e(silu(G_e h) * U_e h)``; ``sh = (1 /
num_shared_experts) * sum over the shared experts of D_s(silu(G_s h) * U_s
h)``, each ``intermediate_size`` wide; ``moe(h) = r + sh``.

The reference is straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``: the rotary formula as
published, a dense masked softmax (a kv head's query heads and a block of
query rows at a time), every held expert computed for every token and
weighted by the router (an expert and a block of tokens at a time), the
shared experts computed APART and averaged, the head a block of the
vocabulary at a time (so that the check's 8 200 tokens fit beside a full
chip), no cache, no kernel, no batching, no window in the walk.  It shares
only the names of the weight tree with the program (whose modules are
imported inside the builder functions alone); the sizes come from the
configuration file's keys.

Departures from the published model, each stated in the file too:

- the chip's share: the file's ``num_experts`` counts the experts HELD
  here, its ``published.num_experts`` is the router's width.  A pick that
  lives on another chip adds nothing, in the program and here alike, and
  that partial result goes on to the next layer (model-configs guide,
  section 4);
- the weight tree is the SERVED one: the sliding layers' ``q_proj`` and
  ``k_proj`` hold each head's columns evens first, then odds
  (``ops/rope.py pairs_to_halves``, over which the program's half-split
  rotation gives the published scores), and the four shared experts lie
  side by side in one ``gate_up`` / ``down`` pair.  The reference puts a
  head's columns back in the published order (``_published_order``) and
  cuts the shared experts apart (``_shared_mean``) before it computes
  anything;
- ``first_k_dense_replace`` is 0: no layer is dense, and
  ``prefix_dense_intermediate_size`` sizes nothing;
- the vision tower is left out (the file's ``assumed.towers``).
"""

from __future__ import annotations

import dataclasses
import functools
import importlib

import jax
import jax.numpy as jnp

from benchmark.archs import glm4_moe_lite
from benchmark.archs.dots3_note import _touched  # the same share, the same count

#: what the file must say for the program to be the model: each is a switch
#: the family has and this model leaves one way
BUILT = {"attention_bias": False, "expert_selection_fn": "sigmoid",
         "first_k_dense_replace": 0, "hidden_act": "silu",
         "norm_topk_prob": True, "position_embedding_type": "rope_gptj",
         "rotary_pct": 1, "shared_expert_combination_strategy": "average",
         "tie_word_embeddings": True, "use_gated_activation": True,
         "use_parallel_block": True, "use_qk_norm": False, "logit_scale": 1,
         "order_of_interleaved_layers": "local_attn_first"}
KINDS = ("sliding_attention", "full_attention")


def _router_width(config: dict) -> int:
    return (config.get("published") or {}).get("num_experts",
                                                config["num_experts"])


def _layer_mix(config: dict) -> dict:
    """{sliding, full: layers} among the file's ``num_hidden_layers``, in
    the mix its ``stage_layers`` state (one period: three sliding layers
    and a full one).  At the file's own depth these are its layers; a
    model twice as deep has those of two periods, so that every count is
    linear in depth, as the harness's contract has it."""
    stage = config["stage_layers"]
    share = config["num_hidden_layers"] / sum(stage.values())
    return {k: v * share for k, v in stage.items()}


# ---------------------------------------------------------------- builder
def program_config(config: dict):
    """The published keys of a configuration file -> the program's
    ``TransformerConfig``, with the expert share the file states.  Where
    the file names the program's own constant for this model
    (``program_config``), the two must agree on everything but depth and
    the share: a width that drifted in the program is an error here."""
    from vllm_omni_tpu.models.cohere2_moe import expert_share
    from vllm_omni_tpu.models.common.transformer import TransformerConfig

    for key, want in BUILT.items():
        if config[key] != want:
            raise SystemExit(f"benchmark: {key} {config[key]!r} is not "
                             f"built (only {want!r})")
    kinds = tuple(config["layer_types"])
    layers = config["num_hidden_layers"]
    if len(kinds) != layers or set(kinds) - set(KINDS):
        raise SystemExit(f"benchmark: layer_types {kinds} are not "
                         f"{layers} layers of {KINDS}")
    shared = config["num_shared_experts"]
    cfg = expert_share(TransformerConfig(
        vocab_size=config["vocab_size"],
        hidden_size=config["hidden_size"],
        num_layers=layers,
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"],
        intermediate_size=config["intermediate_size"],
        rope_theta=float(config["rope_theta"]),
        rms_eps=config["layer_norm_eps"],
        tie_word_embeddings=True,
        moe=True,
        num_experts=_router_width(config),
        num_experts_per_tok=config["num_experts_per_tok"],
        moe_intermediate_size=config["intermediate_size"],
        moe_renormalize=True,
        shared_expert_size=shared * config["intermediate_size"],
        shared_expert_gate=False,
        shared_expert_scale=1.0 / shared,
        router_scoring="sigmoid",
        layer_kinds=kinds,
        sliding_window=config["sliding_window"],
        rope_kinds=("sliding_attention",),
        parallel_block=True,
        norm_subtract_mean=True,
    ), held=config["num_experts"], offset=config.get("expert_offset", 0))
    stage = config["stage_layers"]
    served = {"sliding": kinds.count("sliding_attention"),
              "full": kinds.count("full_attention")}
    if served != stage:
        raise SystemExit(f"benchmark: stage_layers {stage} are not the "
                         f"{layers} layers served, {served}")
    if config.get("program_config"):
        module, _, name = config["program_config"].partition(":")
        theirs = getattr(importlib.import_module(module), name)
        theirs = expert_share(dataclasses.replace(
            theirs, num_layers=layers,
            layer_kinds=theirs.layer_kinds[:layers]),
            held=cfg.num_experts, offset=cfg.expert_offset)
        if theirs != cfg:
            raise SystemExit(
                f"benchmark: {config['program_config']} differs from the "
                f"published configuration: {theirs} != {cfg}")
    return cfg


#: THE DRAW (``init_weights``): what is done to the program's own
#: initialiser (matrices uniform in +-1/sqrt(fan in), norm weights ones,
#: the embedding normal at 0.02) so that the check can see what it must,
#: and why.  The check (``serve.reference_check``) reads the LARGEST of
#: ``reference_check.new_tokens`` greedy tokens' served logprobs against
#: the float32 reference, under one limit for every cell
#: (``serve.LOGPROB_TOL``, 0.15).  It has to read under the limit for
#: every sound run and over it with the window, the full layer's lack of
#: positions, or the pages' precision taken out of the served path
#: (``scripts/sparse_window_control.py``).  What stood in the way, each
#: found in the readings of PR 50's first session and in a simulation of
#: the served roundings (PERF.md, Findings, PR 50):
#:
#: - *a flat softmax.*  Under the plain draw a score has a deviation of a
#:   third: a softmax over 4 096 or 8 192 keys is their average, next to
#:   nothing whatever the keys were.  ``QK_GAIN``: ``q_proj`` and
#:   ``k_proj`` times ``g`` each, a score then deviates by ``g ** 2 / 3``,
#:   by the layer's place in its period of four.  A sharp softmax
#:   multiplies the relative error of its operands by about its scores'
#:   deviation.  The FIRST layer's operands are exact but for their own
#:   rounding (its input is the embedding), so there the pages' precision
#:   is the largest share of a sound run's error that it can be: 3 there
#:   (float8 operands move the stream by 7-9% where bfloat16 moves it by
#:   0.8-1.0%, a ratio of 8-9; three sharp layers in a row would compound
#:   every other rounding too).  2 on the two sliding layers after it
#:   (flat enough to hand that on unamplified, sharp enough that the keys
#:   past the window matter), 3 on the full layer so that rotating it
#:   shows;
#: - *the head reading the input token back.*  The head is the embedding:
#:   with a final norm of ones the greedy token after a token is that
#:   token (logit 8-16), the served logits are bfloat16 (steps of 0.0625
#:   there), and every sound run read half a step, 0.028-0.034, with the
#:   attention's part of the logit a tenth of it.  ``FINAL_NORM_GAIN``:
#:   the final norm's weight is random signs times 1.125, so that no row
#:   is read back, the greedy token's logit is 6-7.5 (steps of 0.031) and
#:   a logit deviates by 1.44 over the vocabulary;
#: - *a pick swapped between a held and an absent expert.*  This chip
#:   adds the picks that land on its 16 experts; where a rounding swaps a
#:   token's eighth and ninth pick and one of the two is held, its share
#:   of the routed sum goes from an eighth to nothing or to two eighths:
#:   a discontinuity of the SHARE, in reference and program alike, that
#:   the whole layer does not have (there the swap replaces one related
#:   expert by another: ``glm4_moe_lite.EXPERT_OWN_SHARE``).  At a stream
#:   error of 1% it happens to 1-2% of tokens a layer and moved such a
#:   token's stream by 10%: the sound readings of 0.092 and 0.169 of the
#:   first session.  ``ROUTED_DOWN_GAIN``: the routed experts' ``down``
#:   times 1/16, so that a swapped pick moves the stream by 0.6%.  What
#:   the chip's check then no longer sees is this chip's routed part (a
#:   sixteenth of what it was); the shared experts, four times as large,
#:   are seen whole, and the CPU tests hold single picks and the share's
#:   offset at 3e-5 with unrelated experts at full size
#:   (tests/models/test_cohere2_moe.py);
#: - *the largest of eight.*  A token's error under float8 operands is
#:   heavy-tailed (median 0.09, one in ten over 0.26 in the simulation)
#:   and a sound token's is bounded by its roundings (99 in 100 under
#:   0.05): the largest of 8 separates them by 3, of 64 by 6.  The file's
#:   ``reference_check.new_tokens`` is 64.
#:
#: What the chip read (my runs, PR 50, second session, call 125, TPU v5
#: lite x1; the largest of 64 tokens after an 8 192-token prompt; a seed
#: draws the weights and the prompt; PERF.md has the seeds):
#:
#: ===================== ============================ ==================
#: draw                  a sound run                  float8 operands
#: ===================== ============================ ==================
#: **3, 2, 2, 3; down    0.033-0.059 over 18 seeds    0.265, 0.276,
#: / 16 (served)**       (eight of them: 0.0346-      0.285, 0.298,
#:                       0.0588; six in the cell's    0.309, 0.323,
#:                       own runs: 0.0326-0.0455)     0.400, 0.496
#: 3.5, 2, 2, 3          0.061, 0.064, 0.081          0.51, 0.56, 0.65
#: 3, 2, 2, 3; down / 4  0.044, 0.051, 0.063          0.32, 0.33, 0.38
#: ===================== ============================ ==================
#:
#: The window off reads 2.56 and 2.77, rotary on the full layer 1.97 and
#: 1.59 (two seeds each).  The smallest float8 reading is 4.5 times the
#: largest sound one; the limit of 0.15 is 2.5 times the one and under
#: the other by 1.8.  Over the first 8 of the same tokens a sound run read
#: 0.015-0.047 and float8 operands 0.126-0.323: the largest of 8 does not
#: separate them.  The first session's draw (2 on the sliding layers, 3 on
#: the full one, a final norm of ones) read 0.028-0.034 sound and 0.032
#: with float8 operands: every reading was the logit's half step.
QK_GAIN = (3.0, 2.0, 2.0, 3.0)
ROUTED_DOWN_GAIN = 0.0625
FINAL_NORM_GAIN = 1.125
#: The routers' columns are drawn in families: expert ``e`` belongs to
#: family ``e mod (width / ROUTER_FAMILY)`` (16 families of 8 at the
#: published 128), and its column is ``sqrt(1 - a^2)`` its family's
#: direction and ``a = ROUTER_OWN_SHARE`` its own draw.  Experts of a family
#: are picked together, and the 16 experts held here (ids 0-15) are ONE OF
#: EACH family, so that about ONE of every token's eight picks lands here
#: whatever the token or the seed.  AN ASSUMPTION (the file's
#: ``assumed.weights``), and what it takes away: a deployment balances its
#: chips' loads by where it places the experts (DeepSeek-V3's EPLB,
#: github.com/deepseek-ai/EPLB, packs experts onto devices so that every
#: device's expected load is equal), which makes a chip's MEAN share of
#: the picks an eighth; the families make it an eighth on every token and
#: remove the variance around it.  Why: the cell's prompts are seeded
#: printable bytes (95 distinct tokens) through a random stack, whose
#: later layers feed every token of a sequence nearly one vector, so
#: under the plain draw all tokens of a sequence picked the same experts
#: and the share that landed here followed the seed and the sequence
#: (9-17%; the held experts' grouped matmuls moved by a quarter between
#: seeds, a request's life by 2.6% and, through where the 45 s window then
#: falls in the mix's cycle of prompt lengths, ``tok_s`` by 7%: PERF.md,
#: PR 50).  ``tok_s`` of this cell is therefore read AT A FIXED LOAD OF
#: ONE PICK A TOKEN on this chip; which of the 16 held experts a chunk's
#: tokens touch is still the draw's (``scripts/sparse_window_control.py
#: --picks`` counts it on the chip).
ROUTER_FAMILY = 8
ROUTER_OWN_SHARE = 0.25


def init_weights(key, cfg, dtype):
    """The served tree: the program's own draw (matrices uniform in
    +-1/sqrt(fan in), norm weights ones), the routed experts of a layer
    related as ``glm4_moe_lite.init_weights`` relates them and for its
    reason (``EXPERT_OWN_SHARE``: a renormalised sigmoid router's marginal
    pick weighs as much as its best, and a rounding that swaps it would
    otherwise swap in an unrelated function) and their ``down`` times
    ``ROUTED_DOWN_GAIN``, q and k times ``QK_GAIN``, the final norm's
    weight random signs times ``FINAL_NORM_GAIN``, the routers' columns in
    families (``ROUTER_FAMILY``), and the sliding layers' q and k columns
    taken from the published order (a head's dims as neighbouring pairs)
    to the served one (``pairs_to_halves``): the draw stands for the
    checkpoint's, the tree is what a loader would hand the program."""
    from vllm_omni_tpu.ops.rope import pairs_to_halves

    params = glm4_moe_lite.init_weights(key, cfg, dtype)
    width = cfg.router_experts or cfg.num_experts
    families = width // ROUTER_FAMILY
    signs = jnp.where(jax.random.bernoulli(
        jax.random.fold_in(key, 49), 0.5, (cfg.hidden_size,)), 1.0, -1.0)
    params["final_norm"]["w"] = (FINAL_NORM_GAIN * signs).astype(dtype)
    for i, (layer, kind) in enumerate(zip(params["layers"],
                                          cfg.layer_types)):
        layer["experts"]["down"] = layer["experts"]["down"] * jnp.asarray(
            ROUTED_DOWN_GAIN, dtype)
        for name in ("q_proj", "k_proj"):
            w = layer[name]["w"] * jnp.asarray(
                QK_GAIN[i % len(QK_GAIN)], dtype)
            if kind in (cfg.rope_kinds or ()):
                w = pairs_to_halves(w, cfg.head_dim)
            layer[name]["w"] = w
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
#: tokens an expert (routed or shared) is computed for at a time: beside a
#: float32 expert (0.2 GB) a block's gate, up and product are
#: 3 x 1024 x 4096 x 4 B = 50 MB
TOKEN_BLOCK = 1024
#: query rows of the dense masked softmax computed at a time, for the 16
#: query heads of one kv head: 16 x 256 x 8200 scores x 4 B = 0.13 GB
QUERY_BLOCK = 256
#: rows of the vocabulary the head takes at a time: 8 192 x 4096 x 4 B =
#: 0.13 GB of the embedding in float32, where the whole is 4.3 GB
VOCAB_BLOCK = 8192


def _f32(a):
    return a.astype(jnp.float32)


def _ln(x, w, eps):
    x = x - jnp.mean(x, -1, keepdims=True)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _blocks(fn, x, block):
    """``fn`` over blocks of ``block`` leading rows of ``x`` (padded with
    zeros to whole blocks), one at a time; the rows of the result."""
    n = x.shape[0]
    pad = -n % block
    parts = jnp.pad(x, ((0, pad),) + ((0, 0),) * (x.ndim - 1)).reshape(
        -1, block, *x.shape[1:])
    out = jax.lax.map(fn, parts)
    return out.reshape(-1, *out.shape[2:])[:n]


def _published_order(w, head_dim):
    """A served q or k projection's columns (a head's evens, then its
    odds) back in the checkpoint's order (neighbouring pairs)."""
    return jnp.swapaxes(
        w.reshape(w.shape[0], -1, 2, head_dim // 2), -1, -2).reshape(w.shape)


def _rope_pairs(x, pos, theta):
    """The published rotation of x [T, H, D] at positions ``pos``:
    neighbouring dims paired, over the whole head."""
    d = x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = pos.astype(jnp.float32)[:, None] * inv[None, :]
    c, s = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * c - odd * s, odd * c + even * s],
                     axis=-1).reshape(x.shape)


def _attention(layer, h, head_dim, theta, window):
    """Grouped-query attention of normed rows h [T, hidden], one sequence:
    a dense masked softmax, one kv head's query heads and ``QUERY_BLOCK``
    query rows at a time.  ``theta`` 0: no rotation; ``window`` 0: every
    key at or below the query."""
    t = h.shape[0]
    pos = jnp.arange(t)
    w_q, w_k = _f32(layer["q_proj"]["w"]), _f32(layer["k_proj"]["w"])
    if theta:
        w_q = _published_order(w_q, head_dim)
        w_k = _published_order(w_k, head_dim)
    k = (h @ w_k).reshape(t, -1, head_dim)
    v = (h @ _f32(layer["v_proj"]["w"])).reshape(t, -1, head_dim)
    kv_heads = k.shape[1]
    if theta:
        k = _rope_pairs(k, pos, theta)
    outs = []
    for g in range(kv_heads):
        cols = w_q.shape[1] // kv_heads
        q = (h @ w_q[:, g * cols:(g + 1) * cols]).reshape(t, -1, head_dim)
        if theta:
            q = _rope_pairs(q, pos, theta)
        k_g, v_g = k[:, g], v[:, g]

        def rows(block, k_g=k_g, v_g=v_g):
            q_b, pos_b = block
            s = jnp.einsum("thd,sd->hts", q_b, k_g) / jnp.sqrt(
                float(head_dim))
            ok = pos_b[:, None] >= pos[None, :]
            if window:
                ok &= pos[None, :] > pos_b[:, None] - window
            s = jnp.where(ok[None], s, -jnp.inf)
            return jnp.einsum("hts,sd->thd", jax.nn.softmax(s, -1), v_g)

        pad = -t % QUERY_BLOCK
        q_p = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(
            -1, QUERY_BLOCK, *q.shape[1:])
        # a padding row sits past the sequence and sees every key
        pos_p = jnp.arange(t + pad).reshape(-1, QUERY_BLOCK)
        o = jax.lax.map(rows, (q_p, pos_p))
        outs.append(o.reshape(-1, *o.shape[2:])[:t])
    o = jnp.concatenate(outs, axis=1)                  # [T, H, D]
    return o.reshape(t, -1) @ _f32(layer["o_proj"]["w"])


def _swiglu(h, gate, up, down):
    """``down(silu(gate h) * up h)``, ``TOKEN_BLOCK`` rows at a time."""
    return _blocks(
        lambda rows: (jax.nn.silu(rows @ gate) * (rows @ up)) @ down, h,
        TOKEN_BLOCK)


def _shared_mean(shared, h, n):
    """The mean of the ``n`` shared experts' outputs, each computed apart:
    expert ``s`` is columns ``s I .. (s + 1) I`` of the gate half and of
    the up half of the served ``gate_up``, and those rows of ``down``."""
    gate_up, down = shared["gate_up"]["w"], shared["down"]["w"]
    width = down.shape[0]
    inter = width // n
    y = jnp.zeros_like(h)
    for s in range(n):
        sl = slice(s * inter, (s + 1) * inter)
        y = y + _swiglu(h, _f32(gate_up[:, sl]),
                        _f32(gate_up[:, width + s * inter:
                                     width + (s + 1) * inter]),
                        _f32(down[sl]))
    return y / n


def _mlp(layer, h, top_k, offset, shared):
    """The held experts' part of the routed sum for normed rows h, every
    held expert computed for every token and weighted by the router (zero
    where it did not pick the expert), plus the shared experts' mean."""
    t = h.shape[0]
    scores = jax.nn.sigmoid(h @ _f32(layer["router"]["w"]))
    top_s, top_i = jax.lax.top_k(scores, top_k)
    combine = jnp.zeros_like(scores).at[jnp.arange(t)[:, None], top_i].set(
        top_s / jnp.sum(top_s, -1, keepdims=True))
    experts = layer["experts"]
    held = experts["down"].shape[0]
    inter = experts["down"].shape[1]
    combine = combine[:, offset:offset + held]

    def add_expert(y, xs):
        gate_up, down, w = xs
        out = _swiglu(h, _f32(gate_up[:, :inter]), _f32(gate_up[:, inter:]),
                      _f32(down))
        return y + out * w[:, None], None

    y, _ = jax.lax.scan(add_expert, jnp.zeros_like(h),
                        (experts["gate_up"], experts["down"], combine.T))
    return y + _shared_mean(layer["shared_expert"], h, shared)


@functools.partial(jax.jit, static_argnames=("hp",))
def _layer(layer, x, hp):
    """One parallel block on x [T, hidden] float32; ``hp`` = (eps,
    head_dim, theta or 0, window or 0, top_k, offset, shared experts)."""
    eps, head_dim, theta, window, top_k, offset, shared = hp
    h = _ln(x, _f32(layer["input_norm"]["w"]), eps)
    return (x + _attention(layer, h, head_dim, theta, window)
            + _mlp(layer, h, top_k, offset, shared))


@jax.jit
def _head(h, embed):
    """Logits of rows h over the vocabulary, ``VOCAB_BLOCK`` rows of the
    embedding at a time."""
    logits = _blocks(lambda rows: _f32(rows) @ h.T, embed, VOCAB_BLOCK)
    return logits.T


def _layer_hps(config: dict) -> dict:
    """{layer kind: ``_layer``'s ``hp``} from the file's keys."""
    tail = (config["num_experts_per_tok"], config.get("expert_offset", 0),
            config["num_shared_experts"])
    head = (config["layer_norm_eps"], config["head_dim"])
    return {
        "sliding_attention": head + (float(config["rope_theta"]),
                                     config["sliding_window"]) + tail,
        "full_attention": head + (0.0, 0) + tail}


def reference_logprobs(params, config: dict, token_ids: list[int],
                       last_n: int, tp: int = 1) -> jax.Array:
    """Log-softmax over the vocabulary after the last ``last_n`` positions
    of ``token_ids``: [last_n, vocab] float32.  ``params`` is the served
    weight tree (upcast here, layer by layer, the experts and the head in
    blocks); ``config`` holds the keys of the configuration file."""
    if tp != 1:
        raise SystemExit("benchmark: the Command A+ reference has no "
                         "tensor-parallel weight layout")
    hps = _layer_hps(config)
    ids = jnp.asarray(token_ids, jnp.int32)
    embed = params["embed"]["w"]
    with jax.default_matmul_precision("highest"):
        x = _f32(embed[ids])
        for layer, kind in zip(params["layers"], config["layer_types"]):
            x = _layer(layer, x, hps[kind])
        h = _ln(x[-last_n:], _f32(params["final_norm"]["w"]),
                config["layer_norm_eps"])
        return jax.nn.log_softmax(
            _head(h, embed) * float(config["logit_scale"]), -1)


# ----------------------------------------------------------------- counts
def model_costs(config: dict, dtype_bytes: int = 2) -> dict:
    """Operations and bytes the decoder's algorithm needs on this chip,
    from the sizes of the configuration file (``num_experts`` held here,
    the router at its published width)."""
    h = config["hidden_size"]
    d = config["head_dim"]
    q_dim = config["num_attention_heads"] * d
    kv_dim = config["num_key_value_heads"] * d
    mix = _layer_mix(config)
    layers = mix["sliding"] + mix["full"]
    held, width = config["num_experts"], _router_width(config)
    top_k = config["num_experts_per_tok"]
    attn_params = h * q_dim + 2 * h * kv_dim + q_dim * h
    expert_params = 3 * h * config["intermediate_size"]
    fixed_mlp = h * width + config["num_shared_experts"] * expert_params
    # of a token's top-k picks, those expected to land on a held expert
    picks_here = top_k * held / width
    return {
        "mix": mix, "layers": layers, "held": held, "width": width,
        "top_k": top_k, "q_dim": q_dim,
        "attn_params": attn_params, "fixed_mlp_params": fixed_mlp,
        "expert_params": expert_params,
        "flops_per_token": 2.0 * layers * (
            attn_params + fixed_mlp + picks_here * expert_params),
        "moe_flops_per_token": 2.0 * (fixed_mlp + picks_here * expert_params),
        # a pair a query head, a layer: the score over D numbers and the
        # value over D, a product and a sum each
        "attn_flops_per_pair": 4.0 * q_dim,
        "head_flops_per_row": 2.0 * h * config["vocab_size"],
        "fixed_weight_bytes": dtype_bytes * (
            layers * (attn_params + fixed_mlp) + h * config["vocab_size"]),
        "fixed_mlp_bytes": dtype_bytes * fixed_mlp,
        "expert_bytes": dtype_bytes * expert_params,
        # a cached token in ONE layer of either kind: K and V of every kv
        # head
        "kv_bytes_per_pos": dtype_bytes * 2 * kv_dim,
    }


def _attention_cost(step: dict, costs: dict, layers: float, bound: str,
                    cap: int, dtype_bytes: int) -> tuple[float, float]:
    """One kind's attention over the pairs its bound leaves (``window``:
    the flight record's ``<phase>_ctx_window``, ``LLMEngine._record_step``;
    a record without them, or no bound, counts every visible key): ``4 x
    128`` operations a pair a query head.  Bytes: a decode row reads the
    rows it attends once, a chunk those its bound can reach once, and
    queries come in and outputs go out at the heads' width."""
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
    flops = layers * costs["attn_flops_per_pair"] * (prefill + decode)
    nbytes = layers * (costs["kv_bytes_per_pos"] * kv_read
                       + 2.0 * dtype_bytes * tokens * costs["q_dim"])
    return flops, nbytes


def attn_cost(step: dict, config: dict, dtype_bytes: int = 2
              ) -> tuple[float, float]:
    """The full layers' attention (what runs under ``omni.attn``): every
    key at or below the token, 4 096 B a cached token a layer."""
    costs = model_costs(config, dtype_bytes)
    return _attention_cost(step, costs, costs["mix"]["full"], "full", 0,
                           dtype_bytes)


def attn_window_cost(step: dict, config: dict, dtype_bytes: int = 2
                     ) -> tuple[float, float]:
    """The sliding layers' attention (``omni.attn_window``):
    ``min(ctx, sliding_window)`` keys a token."""
    costs = model_costs(config, dtype_bytes)
    return _attention_cost(step, costs, costs["mix"]["sliding"], "window",
                           config["sliding_window"], dtype_bytes)


def moe_cost(step: dict, config: dict, dtype_bytes: int = 2
             ) -> tuple[float, float]:
    """The MLP of one step (what runs under ``omni.moe``): the router at
    its whole width, the picks that land on held experts and the shared
    experts per token; the router's and the shared experts' weights and
    as many held experts as the step's tokens are expected to touch, read
    once a layer, and the activations in and out."""
    costs = model_costs(config, dtype_bytes)
    tokens = step["prefill_tokens"] + step["decodes"]
    flops = costs["layers"] * tokens * costs["moe_flops_per_token"]
    nbytes = costs["layers"] * (
        _touched(costs, tokens) * costs["expert_bytes"]
        + costs["fixed_mlp_bytes"]
        + 2.0 * dtype_bytes * tokens * config["hidden_size"])
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
    flops = (costs["flops_per_token"] * tokens
             + costs["head_flops_per_row"] * (step["prefills"]
                                              + step["decodes"])
             + sum(p[0] for p in parts))
    nbytes = (costs["fixed_weight_bytes"]
              + costs["layers"] * _touched(costs, tokens)
              * costs["expert_bytes"]
              + sum(p[1] for p in parts)
              + costs["layers"] * costs["kv_bytes_per_pos"] * tokens)
    return flops, nbytes
