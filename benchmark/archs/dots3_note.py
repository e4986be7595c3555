"""The dots3-note-prev decoder (``model_type`` ``dots3_note``), as a
configuration file names it: ``"arch": "benchmark.archs.dots3_note"``.  It
keeps the contract of ``benchmark/archs/qwen3_moe.py`` (``program_config``,
``init_weights``, ``param_specs``, ``reference_logprobs``, ``step_cost``,
``attn_cost``, ``moe_cost``) and adds ``mla_up_cost``, ``indexer_cost`` and
``attn_window_cost`` for the rooflines of the products with ``kv_b``, of the
indexer and of the sliding layers' attention.

The model (residual block ``x += attn(norm1(x)); x += mlp(norm2(x))``;
``h = norm1(x)``; every ``norm`` is ``x * rsqrt(mean(x^2) + eps) * w``, eps
1e-5).  A layer is *full* or *sliding* as ``layer_types`` says.

*Full layer* (H heads, rotary base theta).  ``c_q = norm(h W_qa) *
sqrt(hidden / q_rank)``; ``[q_nope | q_rope]_i = (c_q W_qb)_i``;
``[c_kv' | k_r'] = h W_kva``; ``c_kv = norm(c_kv') * sqrt(hidden /
kv_rank)``; ``q_rope_i`` and ``k_r`` rotated at the token's position
(rotate-half).  ``[k_nope | v]_{s,i} = (c_kv_s W_kvb)_i``.  The indexer:
``qI_j = (c_q W_Iq)_j`` (its first rotary-width dims rotated), ``kI_s =
LayerNorm(h_s W_Ik)`` (the same dims rotated), ``w = h W_Iw * n^-0.5 *
d^-0.5``; ``I[t, s] = sum_j w[t, j] relu(qI[t, j] . kI[s])`` for ``s <=
t``; ``S_t`` the ``index_topk`` largest of ``I[t, .]``, ties to the lower
``s`` (every ``s <= t`` while there are no more).  ``a = softmax over S_t
of (q_nope_i . k_nope_{s,i} + q_rope_i . k_r_s) / sqrt(nope + rope)``;
``o_i = sum_s a v_{s,i}``; ``g = sigmoid(h W_g)``, one number a head;
output ``concat_i(g_i o_i) W_o``.

*Sliding layer*: the same with widths, head count and base of its own, no
indexer, and ``S_t = {s : t - window < s <= t}``.

*MLP.*  The first ``first_k_dense_replace`` layers: ``down(silu(gate x) *
up x)``.  The others: ``s = sigmoid(x W_r)`` in float32 over the router's
published width; the ``num_experts_per_tok`` largest of ``s + b``; weights
``s_e / sum_picks s`` times ``routed_scaling_factor``; ``y = sum over the
picks HELD HERE (ids expert_offset .. + n_routed_experts) of w_e
expert_e(x) + shared(x)``: the chip is one share of an expert-parallel
deployment, and a pick that lives elsewhere adds nothing.

*Head.*  Final norm, untied ``lm_head``.

The reference is straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``, in the EXPANDED form: per-head
keys and values from ``W_kvb`` for every position, a dense masked softmax
(a few heads at a time, so that 4 104 tokens fit beside a full chip), the
selection by ``lax.top_k`` (whose ties go to the lower index), every held
expert computed for every token and masked by the router's weights, no
cache, no kernel, no batching.  It shares only the names of the weight
tree with the program, which serves the ABSORBED form over two kinds of
page, finds the selection's threshold by counting passes and reads the
window inside its kernel.
"""

from __future__ import annotations

import dataclasses
import functools
import importlib

import jax
import jax.numpy as jnp

from benchmark.archs.glm4_moe_lite import (
    EXPERT_OWN_SHARE,  # the same relation, for the same reason
    _f32,
    _rms,
    _rope,
)

#: The latent norms' weights are drawn at ``(rank / hidden) **
#: (LATENT_NORM_POWER / 2)``: 0 is the initialiser's ones, 1 makes the
#: rescaled latents unit size.  What decides it is what the check reads
#: for a sound run against what it reads with the selection, the window or
#: the cache's precision taken out of the served path (limit 0.15; the
#: largest of eight tokens; PERF.md, PR 34, has the seeds and the runs):
#:
#: ===== ===================== ============ ============ ===============
#: power a sound run           selection    window off   float8 operands
#:                             off
#: ===== ===================== ============ ============ ===============
#: 0     0.072-0.188 (6 seeds, 1.158, 0.373 1.267, 1.004 0.226, 0.366
#:       2 over; a float32
#:       stream); 0.143, 0.146
#:       (a bfloat16 one)
#: 0.5   0.028-0.049 (7 seeds) 0.616, 0.624 1.255, 1.098 0.119, 0.156
#: 1     0.027-0.104           0.67: 5 of 8 0.39: 5 of 8 0.016
#:       (12 seeds)            tokens       tokens
#:                             under 0.15   under 0.15
#: ===== ===================== ============ ============ ===============
#:
#: (All on the chip, my runs, PR 34, but power 1's controls: the float32
#: reference against itself on the CPU at the real widths, one seed.)  At
#: 0 the full layers' scores have a deviation of 2, a softmax over some 40
#: of 2 048 keys: an attention layer then multiplies the relative error it
#: is fed by about its scores' deviation, and no precision a bfloat16 path
#: can afford holds the limit (a float32 residual stream was tried: the
#: six seeds above).  At 1 the deviation is 0.3 and the softmax an
#: average: the check then sees neither a float8 cache nor, on most
#: tokens, a missing selection or window.  At 0.5 (0.8 on the full layers,
#: 0.65 on the sliding ones) a sound run reads what it reads at 1, and the
#: selection and the window what they read at 0; float8 operands sit at
#: the limit, which is the harness's and fixed (PERF.md, Open question
#: 10).
LATENT_NORM_POWER = 0.5

KINDS = ("full_attention", "sliding_attention")


# ---------------------------------------------------------------- builder
def _kind_keys(config: dict, kind: str) -> dict:
    """The widths of a kind of layer, from the file's keys."""
    p = "swa_" if kind == "sliding_attention" else ""
    return {"heads": config[p + "num_attention_heads"],
            "qr": config[p + "q_lora_rank"], "r": config[p + "kv_lora_rank"],
            "dn": config[p + "qk_nope_head_dim"],
            "dr": config[p + "qk_rope_head_dim"],
            "dv": config[p + "v_head_dim"],
            "theta": float(config[p + "rope_theta"])}


def program_config(config: dict):
    """The published keys of a configuration file -> the program's
    ``TransformerConfig``.  Where the file names the program's own constant
    for this model (``program_config``), the two must agree on everything
    but depth and the expert share."""
    from vllm_omni_tpu.models.common.transformer import (
        LatentKind,
        TransformerConfig,
    )
    from vllm_omni_tpu.models.dots3_note import expert_share

    for key, want in (("topk_method", "noaux_tc"), ("hidden_act", "silu"),
                      ("attention_bias", False), ("rope_scaling", None),
                      ("attention_gate_type", "headwise"),
                      ("swa_attention_gate_type", "headwise"),
                      ("apply_mla_qkv_lora_rescale", True),
                      ("moe_layer_freq", 1)):
        if config[key] != want:
            raise SystemExit(f"benchmark: {key} {config[key]!r} is not "
                             f"built (only {want!r})")
    layers = config["num_hidden_layers"]
    if len(config["layer_types"]) != layers:
        raise SystemExit(f"benchmark: {len(config['layer_types'])} "
                         f"layer_types for {layers} layers")
    full, slide = (_kind_keys(config, k) for k in KINDS)
    width = config["published"].get("n_routed_experts",
                                    config["n_routed_experts"])
    cfg = TransformerConfig(
        vocab_size=config["vocab_size"],
        hidden_size=config["hidden_size"],
        num_layers=layers,
        num_heads=full["heads"],
        num_kv_heads=config["num_key_value_heads"],
        head_dim=full["dn"] + full["dr"],
        intermediate_size=config["intermediate_size"],
        rope_theta=full["theta"],
        rms_eps=config["rms_norm_eps"],
        tie_word_embeddings=bool(config["tie_word_embeddings"]),
        moe=True,
        num_experts=width,
        num_experts_per_tok=config["num_experts_per_tok"],
        moe_intermediate_size=config["moe_intermediate_size"],
        moe_renormalize=bool(config["norm_topk_prob"]),
        shared_expert_size=(config["n_shared_experts"]
                            * config["moe_intermediate_size"]),
        shared_expert_gate=False,
        first_k_dense_replace=config["first_k_dense_replace"],
        router_scoring=config["scoring_func"],
        router_bias=True,
        routed_scaling_factor=float(config["routed_scaling_factor"]),
        q_lora_rank=full["qr"], kv_lora_rank=full["r"],
        qk_nope_head_dim=full["dn"], qk_rope_head_dim=full["dr"],
        v_head_dim=full["dv"],
        layer_kinds=tuple(config["layer_types"]),
        sliding=LatentKind(slide["heads"], slide["qr"], slide["r"],
                           slide["dn"], slide["dr"], slide["dv"],
                           slide["theta"]),
        sliding_window=config["sliding_window_size"],
        index_n_heads=config["index_n_heads"],
        index_head_dim=config["index_head_dim"],
        index_topk=config["index_topk"],
        mla_lora_rescale=True, attn_head_gate=True,
    )
    if config.get("program_config"):
        module, _, name = config["program_config"].partition(":")
        theirs = getattr(importlib.import_module(module), name)
        theirs = dataclasses.replace(
            theirs, num_layers=layers,
            layer_kinds=theirs.layer_kinds[:layers])
        if theirs != cfg:
            raise SystemExit(
                f"benchmark: {config['program_config']} differs from the "
                f"published configuration: {theirs} != {cfg}")
    if sum(_layer_mix(config).values()) != layers:
        raise SystemExit(f"benchmark: stage_layers {config['stage_layers']} "
                         f"are not the {layers} layers served")
    return expert_share(cfg, held=config["n_routed_experts"],
                        offset=config.get("expert_offset", 0))


def init_weights(key, cfg, dtype):
    """The program's own draw, with two things changed.  The routed experts
    of a layer are related as GLM-4.7-Flash's are
    (``glm4_moe_lite.EXPERT_OWN_SHARE`` has the reason: a renormalised
    sigmoid router's marginal pick weighs as much as its best).  And the
    latent norms' weights are ``(rank / hidden) ** (LATENT_NORM_POWER /
    2)`` where the initialiser writes ones (the table at
    ``LATENT_NORM_POWER`` has the readings that decide it).  The rescale
    itself stays in program and reference: a missing one would flatten
    the scores sevenfold."""
    from vllm_omni_tpu.models.common import transformer as tfm

    params = tfm.init_params(key, cfg=cfg, dtype=dtype)
    a = EXPERT_OWN_SHARE

    def related(w):
        # an expert at a time, as glm4_moe_lite.init_weights has it (and
        # why): expert 0's draw stands for the shared part
        shared = (1.0 - a * a) ** 0.5 * w[0].astype(jnp.float32)
        return jax.lax.map(
            lambda own: (shared + a * own.astype(jnp.float32)
                         ).astype(w.dtype), w)

    for layer in params["layers"]:
        if "experts" in layer:
            layer["experts"] = jax.tree.map(related, layer["experts"])
        for name in ("q_a_norm", "kv_a_norm"):
            w = layer["mla"][name]["w"]
            layer["mla"][name]["w"] = (w * (w.shape[0] / cfg.hidden_size) ** (
                LATENT_NORM_POWER / 2)).astype(w.dtype)
    return params


def param_specs(shapes):
    raise SystemExit("benchmark: the latent cache has no tensor-parallel "
                     "layout; tensor_parallel_size is 1")


# -------------------------------------------------------------- reference
#: experts upcast to float32 at a time, heads attended at a time, columns
#: of the dense MLP and of the head at a time: each bounds a transient to a
#: few hundred MB beside a full chip (4 104 tokens: 4 heads' scores are
#: 270 MB, 8 experts' activations 0.5 GB, a 3 456-column slice of the
#: dense MLP's matrices 0.2 GB, a 19 008-column slice of the head 0.4 GB)
EXPERT_CHUNK = 8
HEAD_CHUNK = 4
MLP_CHUNK = 3456
VOCAB_CHUNK = 19008


def _swiglu(h, gate_up, down, chunk=MLP_CHUNK):
    """``down(silu(gate h) * up h)``, the intermediate columns in blocks."""
    inter = down.shape[0]
    y = jnp.zeros(h.shape[:-1] + (down.shape[1],), jnp.float32)
    for c0 in range(0, inter, chunk):
        c1 = min(c0 + chunk, inter)
        gate = h @ _f32(gate_up[:, c0:c1])
        up = h @ _f32(gate_up[:, inter + c0:inter + c1])
        y = y + (jax.nn.silu(gate) * up) @ _f32(down[c0:c1])
    return y


def _sparse_mlp(layer, h, top_k, renorm, scale, offset):
    """The held experts' and the shared expert's sum for normed rows h:
    the router over its whole width, every held expert computed for every
    token, masked by the router's weights."""
    t = h.shape[0]
    scores = jax.nn.sigmoid(h @ _f32(layer["router"]["w"]))
    _, top_i = jax.lax.top_k(scores + _f32(layer["router"]["e_bias"]), top_k)
    top_w = jnp.take_along_axis(scores, top_i, axis=-1)
    if renorm:
        top_w = top_w / jnp.sum(top_w, -1, keepdims=True)
    combine = jnp.zeros_like(scores).at[jnp.arange(t)[:, None], top_i].set(
        top_w * scale)
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
    return y + _swiglu(h, layer["shared_expert"]["gate_up"]["w"],
                       layer["shared_expert"]["down"]["w"])


def _by_heads(fn, *arrays):
    """``fn`` over ``HEAD_CHUNK`` heads at a time; ``arrays`` are
    [heads, ...], and so is the result."""
    heads = arrays[0].shape[0]
    chunk = HEAD_CHUNK if heads % HEAD_CHUNK == 0 else 1
    parts = [a.reshape(heads // chunk, chunk, *a.shape[1:]) for a in arrays]
    out = jax.lax.map(lambda xs: fn(*xs), tuple(parts))
    return out.reshape(heads, *out.shape[2:])


def _join(a, b):
    """``a | b`` on the last axis.  Two halves of one width are stacked
    and reshaped, not concatenated: see benchmark/archs/qwen3_moe.py
    ``_rope`` (the unaligned join aborts the TPU compiler)."""
    if a.shape[-1] == b.shape[-1]:
        return jnp.stack([a, b], axis=-2).reshape(
            *a.shape[:-1], 2 * a.shape[-1])
    return jnp.concatenate([a, b], axis=-1)


def _selection(ix, h, c_q, pos, hp):
    """[T, T] bool: the keys each token's indexer keeps."""
    dr, theta, eps, top_k = hp
    t = h.shape[0]
    n = ix["w"]["w"].shape[1]
    q = (c_q @ _f32(ix["wq"]["w"])).reshape(t, n, -1)
    d = q.shape[-1]
    q = _join(_rope(q[..., :dr], pos, theta), q[..., dr:])
    k = h @ _f32(ix["wk"]["w"])
    k = k - jnp.mean(k, -1, keepdims=True)
    k = (k * jax.lax.rsqrt(jnp.mean(k * k, -1, keepdims=True) + eps)
         * _f32(ix["k_norm"]["w"]) + _f32(ix["k_norm"]["b"]))
    k = _join(_rope(k[:, None, :dr], pos, theta)[:, 0], k[:, dr:])
    w = (h @ _f32(ix["w"]["w"])) * (n ** -0.5 * d ** -0.5)
    # the sum over the heads, a few at a time
    chunk = HEAD_CHUNK if n % HEAD_CHUNK == 0 else 1

    def add_heads(acc, xs):
        qj, wj = xs  # [chunk, T, d], [chunk, T]
        dots = jax.nn.relu(jnp.einsum("htd,sd->hts", qj, k))
        return acc + jnp.einsum("ht,hts->ts", wj, dots), None

    scores, _ = jax.lax.scan(
        add_heads, jnp.zeros((t, t), jnp.float32),
        (jnp.moveaxis(q, 1, 0).reshape(n // chunk, chunk, t, d),
         w.T.reshape(n // chunk, chunk, t)))
    causal = pos[:, None] >= pos[None, :]
    if t <= top_k:
        return causal
    _, top_i = jax.lax.top_k(jnp.where(causal, scores, -jnp.inf), top_k)
    picked = jnp.zeros((t, t), bool).at[jnp.arange(t)[:, None], top_i].set(
        True)
    return picked & causal


@functools.partial(jax.jit, static_argnames=("hp",))
def _layer(layer, x, hp):
    """One block on x [T, hidden] float32, attention in the expanded form.
    ``hp`` = (qk_nope, qk_rope, v_head_dim, kv rank, q rank, theta, window
    or 0, eps, index_topk, top_k, renormalise, scaling factor, expert
    offset)."""
    (dn, dr, dv, r, qr, theta, window, eps, index_topk, top_k, renorm,
     scale, offset) = hp
    m = layer["mla"]
    t, hidden = x.shape
    pos = jnp.arange(t)
    h = _rms(x, _f32(layer["input_norm"]["w"]), eps)
    c_q = _rms(h @ _f32(m["q_a"]["w"]), _f32(m["q_a_norm"]["w"]), eps) \
        * (hidden / qr) ** 0.5
    q = (c_q @ _f32(m["q_b"]["w"])).reshape(t, -1, dn + dr)
    kv = h @ _f32(m["kv_a"]["w"])
    c_kv = _rms(kv[:, :r], _f32(m["kv_a_norm"]["w"]), eps) \
        * (hidden / r) ** 0.5
    k_rope = _rope(kv[:, None, r:], pos, theta)[:, 0]           # [T, dr]
    q_rope = _rope(q[..., dn:], pos, theta)
    # every position's per-head key and value, from its latent row
    kv_h = (c_kv @ _f32(m["kv_b"]["w"])).reshape(t, -1, dn + dv)
    if "indexer" in layer:
        allowed = _selection(layer["indexer"], h, c_q, pos,
                             (dr, theta, eps, index_topk))
    else:
        allowed = pos[:, None] >= pos[None, :]
    if window:
        allowed &= pos[None, :] > pos[:, None] - window

    def heads(qn, qp, kn, vh):
        s = (jnp.einsum("htd,hsd->hts", qn, kn)
             + jnp.einsum("htd,sd->hts", qp, k_rope)) / jnp.sqrt(
                 float(dn + dr))
        s = jnp.where(allowed[None], s, -jnp.inf)
        return jnp.einsum("hts,hsd->htd", jax.nn.softmax(s, -1), vh)

    def hm(a):
        return jnp.moveaxis(a, 1, 0)

    o = hm(_by_heads(heads, hm(q[..., :dn]), hm(q_rope), hm(kv_h[..., :dn]),
                     hm(kv_h[..., dn:])))                      # [T, H, dv]
    gate = jax.nn.sigmoid(h @ _f32(layer["attn_gate"]["w"]))
    x = x + (o * gate[..., None]).reshape(t, -1) @ _f32(layer["o_proj"]["w"])
    h = _rms(x, _f32(layer["post_norm"]["w"]), eps)
    if "experts" in layer:
        return x + _sparse_mlp(layer, h, top_k, renorm, scale, offset)
    return x + _swiglu(h, layer["gate_up"]["w"], layer["down"]["w"])


@jax.jit
def _head(params, x, eps):
    """Log-softmax over the vocabulary, the head's columns in blocks."""
    h = _rms(x, _f32(params["final_norm"]["w"]), eps)
    w = params["lm_head"]["w"]
    logits = jnp.concatenate(
        [h @ _f32(w[:, c0:c0 + VOCAB_CHUNK])
         for c0 in range(0, w.shape[1], VOCAB_CHUNK)], axis=-1)
    return jax.nn.log_softmax(logits, -1)


def reference_logprobs(params, config: dict, token_ids: list[int],
                       last_n: int, tp: int = 1) -> jax.Array:
    """Log-softmax over the vocabulary after the last ``last_n`` positions
    of ``token_ids``: [last_n, vocab] float32.  ``params`` is the served
    weight tree (upcast here, layer by layer); ``config`` holds the keys of
    the configuration file."""
    if tp != 1:
        raise SystemExit("benchmark: the dots3-note reference has no "
                         "tensor-parallel weight layout")
    eps = config["rms_norm_eps"]
    tail = (eps, config["index_topk"], config["num_experts_per_tok"],
            bool(config["norm_topk_prob"]),
            float(config["routed_scaling_factor"]),
            config.get("expert_offset", 0))
    hps = {}
    for kind in KINDS:
        k = _kind_keys(config, kind)
        window = (config["sliding_window_size"]
                  if kind == "sliding_attention" else 0)
        hps[kind] = (k["dn"], k["dr"], k["dv"], k["r"], k["qr"], k["theta"],
                     window) + tail
    ids = jnp.asarray(token_ids, jnp.int32)
    with jax.default_matmul_precision("highest"):
        x = params["embed"]["w"][ids].astype(jnp.float32)
        for layer, kind in zip(params["layers"], config["layer_types"]):
            x = _layer(layer, x, hps[kind])
        return _head({k: params[k] for k in ("final_norm", "lm_head")},
                     x[-last_n:], eps)


# ----------------------------------------------------------------- counts
def _layer_mix(config: dict) -> dict:
    """{dense_full, sparse_full, sparse_sliding: layers} among the file's
    ``num_hidden_layers``, in the mix its ``stage_layers`` state (the
    leading dense full layer and one period: a sparse full layer and three
    sparse sliding ones).  At the file's own depth these are its layers; a
    model twice as deep counts two such stages, so that every count of the
    layers is linear in depth, as the harness's contract has it."""
    stage = config["stage_layers"]
    share = config["num_hidden_layers"] / sum(stage.values())
    return {k: v * share for k, v in stage.items()}


def model_costs(config: dict, dtype_bytes: int = 2) -> dict:
    """Operations and bytes the decoder's algorithm needs on this chip,
    from the sizes of the configuration file (``n_routed_experts`` held
    here, the router at its published width).  A padded lane is the
    program's cost and is in none of these."""
    h = config["hidden_size"]
    mix = _layer_mix(config)
    n = {"full_attention": mix["dense_full"] + mix["sparse_full"],
         "sliding_attention": mix["sparse_sliding"]}
    sparse = mix["sparse_full"] + mix["sparse_sliding"]
    ni, di = config["index_n_heads"], config["index_head_dim"]
    kinds = {}
    for kind in KINDS:
        k = _kind_keys(config, kind)
        # a layer's projections without kv_b: q_a, q_b, kv_a, o, the gate
        k["proj_params"] = (
            h * k["qr"] + k["qr"] * k["heads"] * (k["dn"] + k["dr"])
            + h * (k["r"] + k["dr"]) + k["heads"] * k["dv"] * h
            + h * k["heads"])
        k["kvb_params"] = k["r"] * k["heads"] * (k["dn"] + k["dv"])
        k["layers"] = n[kind]
        kinds[kind] = k
    index_params = kinds["full_attention"]["qr"] * ni * di + h * di + h * ni
    held = config["n_routed_experts"]
    width = config["published"].get("n_routed_experts", held)
    top_k = config["num_experts_per_tok"]
    expert_params = 3 * h * config["moe_intermediate_size"]
    sparse_fixed = h * width + config["n_shared_experts"] * expert_params
    dense_params = 3 * h * config["intermediate_size"]
    picks_here = top_k * held / width
    mixer_params = sum(k["layers"] * (k["proj_params"] + k["kvb_params"])
                       for k in kinds.values()) \
        + n["full_attention"] * index_params
    return {
        "kinds": kinds, "sparse": sparse, "dense": mix["dense_full"],
        "held": held, "width": width, "top_k": top_k,
        "index_params": index_params, "ni": ni, "di": di,
        "flops_per_token": 2.0 * (
            mixer_params + mix["dense_full"] * dense_params
            + sparse * (sparse_fixed + picks_here * expert_params)),
        "moe_flops_per_token": 2.0 * (sparse_fixed
                                      + picks_here * expert_params),
        "head_flops_per_row": 2.0 * h * config["vocab_size"],
        "fixed_weight_bytes": dtype_bytes * (
            mixer_params + mix["dense_full"] * dense_params
            + sparse * sparse_fixed + h * config["vocab_size"]),
        "sparse_fixed_bytes": dtype_bytes * sparse_fixed,
        "expert_bytes": dtype_bytes * expert_params,
    }


def _touched(costs: dict, tokens: float) -> float:
    """Held experts that ``tokens`` tokens are expected to route to."""
    return costs["held"] * (
        1.0 - (1.0 - costs["top_k"] / costs["width"]) ** tokens)


def _pairs(step: dict, bound: str) -> tuple[float, float]:
    """(prefill, decode) attended pairs of a step under a bound (``kept``:
    the indexer's selection; ``window``): the flight record's
    ``<phase>_ctx_<bound>`` (``LLMEngine._record_step``); a record without
    them counts every visible key."""
    return (step.get(f"prefill_ctx_{bound}", step["prefill_ctx"]),
            step.get(f"decode_ctx_{bound}", step["decode_ctx"]))


def _attention(step: dict, k: dict, bound: str, cap: int,
               dtype_bytes: int) -> tuple[float, float]:
    """One kind's attention over the pairs its bound leaves, each kind of
    row at its cheaper published form whatever the program runs: a decode
    row absorbed (2 x H x (2r + dr) a pair over latent rows of r + dr
    numbers), a prefill token expanded (2 x H x (dn + dr + dv) a pair).
    Bytes: a decode row reads the rows it attends once, a chunk those its
    bound can reach once, queries in and outputs out at the form's
    widths."""
    prefill, decode = _pairs(step, bound)
    heads, r, dn, dr, dv = (k[x] for x in ("heads", "r", "dn", "dr", "dv"))
    flops = k["layers"] * 2.0 * heads * (
        prefill * (dn + dr + dv) + decode * (2 * r + dr))
    kv_read = decode
    if step["prefill_tokens"] > 0:
        reach = (step["prefills"] * step["prefill_ctx"]
                 / step["prefill_tokens"] + step["prefill_tokens"] / 2.0)
        kv_read += min(reach, step["prefills"] * cap
                       + step["prefill_tokens"])
    q_out = heads * (step["decodes"] * (2 * r + dr)
                     + step["prefill_tokens"] * (dn + dr + dv))
    return flops, dtype_bytes * k["layers"] * ((r + dr) * kv_read + q_out)


def attn_cost(step: dict, config: dict, dtype_bytes: int = 2
              ) -> tuple[float, float]:
    """The full layers' attention (what runs under ``omni.attn``) over the
    keys the ALGORITHM attends: ``min(ctx, index_topk)`` a token, whatever
    a masked dense walk touches."""
    costs = model_costs(config, dtype_bytes)
    return _attention(step, costs["kinds"]["full_attention"], "kept",
                      config["index_topk"], dtype_bytes)


def attn_window_cost(step: dict, config: dict, dtype_bytes: int = 2
                     ) -> tuple[float, float]:
    """The sliding layers' attention (``omni.attn_window``):
    ``min(ctx, window)`` keys a token."""
    costs = model_costs(config, dtype_bytes)
    return _attention(step, costs["kinds"]["sliding_attention"], "window",
                      config["sliding_window_size"], dtype_bytes)


def indexer_cost(step: dict, config: dict, dtype_bytes: int = 2
                 ) -> tuple[float, float]:
    """What runs under ``omni.indexer`` in one step, on the full layers:
    the indexer's three projections a token, and for every (token, visible
    key) pair the heads' products and their weighted rectified sum (2 x n x
    d + 2 x n).  Bytes: the indexer's weights once a layer, the whole
    context's index keys once a decode row and once a chunk, the queries
    and weights in, and one mark a pair out (the selection)."""
    costs = model_costs(config, dtype_bytes)
    full = costs["kinds"]["full_attention"]["layers"]
    ni, di = costs["ni"], costs["di"]
    tokens = step["prefill_tokens"] + step["decodes"]
    pairs = step["prefill_ctx"] + step["decode_ctx"]
    keys_read = step["decode_ctx"]
    if step["prefill_tokens"] > 0:
        keys_read += (step["prefills"] * step["prefill_ctx"]
                      / step["prefill_tokens"] + step["prefill_tokens"] / 2.0)
    flops = full * (2.0 * tokens * costs["index_params"]
                    + pairs * (2.0 * ni * di + 2.0 * ni))
    nbytes = full * (dtype_bytes * (costs["index_params"] + di * keys_read
                                    + tokens * ni * (di + 2))
                     + pairs / 8.0)
    return flops, nbytes


def mla_up_cost(step: dict, config: dict, dtype_bytes: int = 2
                ) -> tuple[float, float]:
    """What runs under ``omni.mla_up`` in one step: every product with
    either kind's ``kv_b``, each token's once a layer, as
    ``glm4_moe_lite.mla_up_cost`` counts it."""
    costs = model_costs(config, dtype_bytes)
    tokens = step["prefill_tokens"] + step["decodes"]
    flops = nbytes = 0.0
    for k in costs["kinds"].values():
        heads, r, dn, dv = k["heads"], k["r"], k["dn"], k["dv"]
        flops += 2.0 * k["layers"] * tokens * k["kvb_params"]
        nbytes += dtype_bytes * k["layers"] * (
            k["kvb_params"]
            + step["prefill_tokens"] * (r + heads * (dn + dv))
            + step["decodes"] * heads * (dn + 2 * r + dv))
    return flops, nbytes


def moe_cost(step: dict, config: dict, dtype_bytes: int = 2
             ) -> tuple[float, float]:
    """The sparse MLPs of one step (``omni.moe``; the leading dense layer
    is ``omni.mlp``): the router at its whole width, the picks that land
    on held experts, the shared expert; the router's and the shared
    expert's weights and as many held experts as the step's tokens are
    expected to touch, read once a layer, and the activations in and out."""
    costs = model_costs(config, dtype_bytes)
    tokens = step["prefill_tokens"] + step["decodes"]
    flops = costs["sparse"] * tokens * costs["moe_flops_per_token"]
    nbytes = costs["sparse"] * (
        _touched(costs, tokens) * costs["expert_bytes"]
        + costs["sparse_fixed_bytes"]
        + 2.0 * dtype_bytes * tokens * config["hidden_size"])
    return flops, nbytes


def step_cost(step: dict, config: dict, mean_prefill_ctx: float,
              mean_decode_ctx: float, dtype_bytes: int = 2
              ) -> tuple[float, float] | None:
    """One whole step (``prefill_tokens``, ``decodes``, ``prefills``), as
    ``qwen3_moe.step_cost`` counts it: every weight it touches read once
    (of the held experts as many as its tokens are expected to route to),
    the attention of both kinds over the keys their bounds leave, the
    indexer over every visible key, and the rows written.  ``mean_*_ctx``
    stand in where the record holds no pairs of its own (a mix's means:
    the bounds then cap the means)."""
    costs = model_costs(config, dtype_bytes)
    tokens = step["prefill_tokens"] + step["decodes"]
    if tokens <= 0:
        return None
    rec = {"prefill_ctx": step["prefill_tokens"] * mean_prefill_ctx,
           "decode_ctx": step["decodes"] * mean_decode_ctx, **step}
    if "prefill_ctx_kept" not in rec:
        for bound, cap in (("kept", config["index_topk"]),
                           ("window", config["sliding_window_size"])):
            rec[f"prefill_ctx_{bound}"] = step["prefill_tokens"] * min(
                mean_prefill_ctx, cap)
            rec[f"decode_ctx_{bound}"] = step["decodes"] * min(
                mean_decode_ctx, cap)
    parts = [attn_cost(rec, config, dtype_bytes),
             attn_window_cost(rec, config, dtype_bytes),
             indexer_cost(rec, config, dtype_bytes)]
    written = dtype_bytes * tokens * sum(
        k["layers"] * (k["r"] + k["dr"]) for k in costs["kinds"].values())
    flops = (costs["flops_per_token"] * tokens
             + costs["head_flops_per_row"] * (step["prefills"]
                                              + step["decodes"])
             + sum(p[0] for p in parts)
             # the indexer's projections are in flops_per_token already
             - 2.0 * costs["kinds"]["full_attention"]["layers"] * tokens
             * costs["index_params"])
    nbytes = (costs["fixed_weight_bytes"]
              + costs["sparse"] * _touched(costs, tokens)
              * costs["expert_bytes"]
              + sum(p[1] for p in parts) + written
              - dtype_bytes * costs["kinds"]["full_attention"]["layers"]
              * costs["index_params"])
    return flops, nbytes
