"""The Qwen3-MoE decoder (the Qwen3-Omni thinker's text model), as a
configuration file names it: ``"arch": "benchmark.archs.qwen3_moe"``.

An architecture module is one model family's whole presence in the
benchmark; the harness (run.py, serve.py, spec.py, trace.py, readers.py,
span_readers.py) names none and finds this one through the file's ``arch``
key (``spec.arch_of``).  A later PR that brings another family adds such a
module beside this one, with its configuration and its cell, and edits
nothing.  Every module offers these names:

``program_config(config)``
    the program's own configuration object from the file's published
    keys; where the file names a constant of the program
    (``program_config``), a width that drifted there is a ``SystemExit``.
``init_weights(key, cfg, dtype)``
    the served weight tree from a PRNG key; the harness jits it once.
``param_specs(shapes)``
    the tree of ``PartitionSpec`` under tensor parallelism, for the
    shapes ``jax.eval_shape`` gives of ``init_weights``.
``reference_logprobs(params, config, token_ids, last_n, tp=1)``
    the plain reference: float32 log-softmax over the vocabulary after the
    last ``last_n`` positions of ``token_ids``, ``[last_n, vocab]``; the
    head is never computed over the rest of a long prompt.
``step_cost(step, config, mean_prefill_ctx, mean_decode_ctx)``,
``attn_cost(step, config)``, ``moe_cost(step, config)``
    ``(operations, bytes)`` that one step, its attention and its sparse
    MLP need by the algorithm, or None where the step has none of that
    work.  A step is a flight-recorder record.  The readers keep the
    peaks, the chips and the division.

The reference here: RMSNorm, GQA with per-head q/k RMSNorm, rotary
positions, softmax-top-k routed experts with renormalised weights, SwiGLU.
Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``: every expert computed
densely, no kernel, no cache, no batching.  It shares only the names of
the weight tree with the program (whose modules are imported inside the
two builder functions alone); the sizes come from the configuration
file's published keys.

Departures from the published model: a text-only prompt gives the three
mrope position streams the same value, which makes mrope the plain rotary
embedding computed here (whether sections are interleaved or contiguous
then changes nothing).
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

    rope = config.get("rope_scaling") or {}
    cfg = TransformerConfig(
        vocab_size=config["vocab_size"],
        hidden_size=config["hidden_size"],
        num_layers=config["num_hidden_layers"],
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"],
        intermediate_size=config["intermediate_size"],
        rope_theta=float(config["rope_theta"]),
        rms_eps=config["rms_norm_eps"],
        mrope_sections=(tuple(rope["mrope_section"])
                        if rope.get("mrope_section") else None),
        qk_norm=bool(config.get("use_qk_norm", False)),
        attention_bias=bool(config.get("attention_bias", False)),
        tie_word_embeddings=bool(config.get("tie_word_embeddings", False)),
        moe=config.get("num_experts", 0) > 0,
        num_experts=config.get("num_experts", 0),
        num_experts_per_tok=config.get("num_experts_per_tok", 0),
        moe_intermediate_size=config.get("moe_intermediate_size", 0),
        moe_renormalize=bool(config.get("norm_topk_prob", True)),
        shared_expert_size=config.get("shared_expert_intermediate_size", 0),
    )
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


def init_weights(key, cfg, dtype):
    from vllm_omni_tpu.models.common import transformer as tfm

    return tfm.init_params(key, cfg=cfg, dtype=dtype)


def param_specs(shapes):
    from vllm_omni_tpu.parallel.sharding import ar_param_specs_tree

    return ar_param_specs_tree(shapes)


# -------------------------------------------------------------- reference
#: experts upcast to float32 at a time: bounds the transient to
#: 16 x (2048 x 1536 + 768 x 2048) x 4 B = 0.3 GB beside a full chip
EXPERT_CHUNK = 16


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, positions, theta):
    """x [T, H, D]; rotate the two halves of D (the "neox" layout)."""
    half = x.shape[-1] // 2
    inv_freq = 1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    # stack + reshape, not concatenate on the last axis: joining two 64-wide
    # halves there aborts the XLA TPU compiler of libtpu 0.0.34 (PR 21)
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                     axis=-2).reshape(x.shape)


@functools.partial(jax.jit, static_argnames=("hp",))
def _layer(layer, x, hp):
    """One block on x [T, hidden] float32; ``hp`` is a hashable tuple of
    (head_dim, eps, theta, top_k, renormalise, tp).  Under tensor
    parallelism the served tree holds the fused gate_up columns as
    [gate_0, up_0, gate_1, up_1, ...], a pair for each shard
    (parallel/sharding.py); they are put back in order here."""
    head_dim, eps, theta, top_k, renorm, tp = hp
    f32 = lambda a: a.astype(jnp.float32)  # noqa: E731
    t = x.shape[0]
    pos = jnp.arange(t)
    h = _rms(x, f32(layer["input_norm"]["w"]), eps)
    q = (h @ f32(layer["q_proj"]["w"])).reshape(t, -1, head_dim)
    k = (h @ f32(layer["k_proj"]["w"])).reshape(t, -1, head_dim)
    v = (h @ f32(layer["v_proj"]["w"])).reshape(t, -1, head_dim)
    if "q_norm" in layer:
        q = _rms(q, f32(layer["q_norm"]["w"]), eps)
        k = _rms(k, f32(layer["k_norm"]["w"]), eps)
    q, k = _rope(q, pos, theta), _rope(k, pos, theta)
    group = q.shape[1] // k.shape[1]
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    s = jnp.einsum("thd,shd->hts", q, k) / jnp.sqrt(float(head_dim))
    s = jnp.where(pos[None, :, None] >= pos[None, None, :], s, -jnp.inf)
    o = jnp.einsum("hts,shd->thd", jax.nn.softmax(s, -1), v)
    x = x + o.reshape(t, -1) @ f32(layer["o_proj"]["w"])
    h = _rms(x, f32(layer["post_norm"]["w"]), eps)
    probs = jax.nn.softmax(h @ f32(layer["router"]["w"]), -1)
    top_w, top_i = jax.lax.top_k(probs, top_k)
    if renorm:
        top_w = top_w / jnp.sum(top_w, -1, keepdims=True)
    n_exp = probs.shape[-1]
    combine = jnp.zeros_like(probs).at[jnp.arange(t)[:, None], top_i].set(
        top_w)
    y = jnp.zeros_like(x)
    for e0 in range(0, n_exp, EXPERT_CHUNK):
        sl = slice(e0, e0 + EXPERT_CHUNK)
        gu = jnp.einsum("th,ehf->etf", h, f32(layer["experts"]["gate_up"][sl]))
        gu = gu.reshape(*gu.shape[:2], tp, 2, -1)
        gate = gu[..., 0, :].reshape(*gu.shape[:2], -1)
        up = gu[..., 1, :].reshape(*gu.shape[:2], -1)
        out = jnp.einsum("etf,efh->eth", jax.nn.silu(gate) * up,
                         f32(layer["experts"]["down"][sl]))
        y = y + jnp.einsum("eth,te->th", out, combine[:, sl])
    return x + y


@jax.jit
def _head(params, x, eps):
    h = _rms(x, params["final_norm"]["w"].astype(jnp.float32), eps)
    logits = h @ params["lm_head"]["w"].astype(jnp.float32)
    return jax.nn.log_softmax(logits, -1)


def reference_logprobs(params, config: dict, token_ids: list[int],
                       last_n: int, tp: int = 1) -> jax.Array:
    """Log-softmax over the vocabulary after the last ``last_n`` positions
    of ``token_ids``: [last_n, vocab] float32.  ``params`` is the served
    weight tree (upcast here, layer by layer); ``config`` holds the
    published keys of the configuration file; ``tp`` its tensor-parallel
    degree."""
    hp = (config["head_dim"], config["rms_norm_eps"],
          float(config["rope_theta"]), config["num_experts_per_tok"],
          bool(config["norm_topk_prob"]), tp)
    ids = jnp.asarray(token_ids, jnp.int32)
    with jax.default_matmul_precision("highest"):
        x = params["embed"]["w"][ids].astype(jnp.float32)
        for layer in params["layers"]:
            x = _layer(layer, x, hp)
        return _head({k: params[k] for k in ("final_norm", "lm_head")},
                     x[-last_n:], config["rms_norm_eps"])


# ----------------------------------------------------------------- counts
def model_costs(config: dict, dtype_bytes: int = 2) -> dict:
    """Operations and bytes the decoder's algorithm needs, from the
    published sizes of the configuration file."""
    h, d = config["hidden_size"], config["head_dim"]
    q_dim, kv_dim = config["num_attention_heads"] * d, \
        config["num_key_value_heads"] * d
    layers, experts = config["num_hidden_layers"], config["num_experts"]
    attn_params = h * q_dim + 2 * h * kv_dim + q_dim * h
    expert_params = 3 * h * config["moe_intermediate_size"]
    return {
        "layers": layers, "experts": experts,
        "top_k": config["num_experts_per_tok"],
        "flops_per_token": 2.0 * layers * (
            attn_params + h * experts
            + config["num_experts_per_tok"] * expert_params),
        "attn_flops_per_pair": 4.0 * q_dim * layers,
        "head_flops_per_row": 2.0 * h * config["vocab_size"],
        "fixed_weight_bytes": dtype_bytes * (
            layers * (attn_params + h * experts) + h * config["vocab_size"]),
        "expert_bytes": dtype_bytes * expert_params,
        "kv_bytes_per_pos": dtype_bytes * 2 * layers * kv_dim,
    }


def step_cost(step: dict, config: dict, mean_prefill_ctx: float,
              mean_decode_ctx: float, dtype_bytes: int = 2
              ) -> tuple[float, float] | None:
    """One whole step (``prefill_tokens``, ``decodes``, ``prefills``).  It
    reads the weights it touches once: all but the experts, and of the
    experts as many as its tokens are expected to route to,
    E x (1 - (1 - k/E)^tokens).  Attention's pairs and the cache read take
    the mean context per prefill token and per decode row that the caller
    gives (the traffic mix's, or the step's own record)."""
    costs = model_costs(config, dtype_bytes)
    e, k = costs["experts"], costs["top_k"]
    tokens = step["prefill_tokens"] + step["decodes"]
    if tokens <= 0:
        return None
    pairs = (step["prefill_tokens"] * mean_prefill_ctx
             + step["decodes"] * mean_decode_ctx)
    flops = (costs["flops_per_token"] * tokens
             + costs["attn_flops_per_pair"] * pairs
             + costs["head_flops_per_row"] * (step["prefills"]
                                              + step["decodes"]))
    touched = e * (1.0 - (1.0 - k / e) ** tokens)
    # the cache is read once per decode row and once per prefill chunk
    kv_read = (step["decodes"] * mean_decode_ctx
               + step["prefills"] * 2 * mean_prefill_ctx)
    nbytes = (costs["fixed_weight_bytes"]
              + costs["layers"] * touched * costs["expert_bytes"]
              + costs["kv_bytes_per_pos"] * (kv_read + tokens))
    return flops, nbytes


def attn_cost(step: dict, config: dict, dtype_bytes: int = 2
              ) -> tuple[float, float]:
    """The attention of one step (a record with ``prefill_ctx`` /
    ``decode_ctx``, the attended pairs of its prefill tokens and of its
    decode rows).  Bytes: every decode row reads its context once
    (``decode_ctx`` positions in all), a prefill chunk reads the context
    it attends once (its tokens' mean context plus half its length, exact
    for one chunk a step), and queries come in and outputs go out; the
    cache write is ``omni.kv_write``'s, not attention's."""
    costs = model_costs(config, dtype_bytes)
    tokens = step["prefill_tokens"] + step["decodes"]
    kv_read = step["decode_ctx"]
    if step["prefill_tokens"] > 0:
        kv_read += (step["prefills"] * step["prefill_ctx"]
                    / step["prefill_tokens"] + step["prefill_tokens"] / 2.0)
    q_dim = config["num_attention_heads"] * config["head_dim"]
    flops = costs["attn_flops_per_pair"] * (step["prefill_ctx"]
                                            + step["decode_ctx"])
    nbytes = (costs["kv_bytes_per_pos"] * kv_read
              + 2.0 * dtype_bytes * costs["layers"] * tokens * q_dim)
    return flops, nbytes


def moe_cost(step: dict, config: dict, dtype_bytes: int = 2
             ) -> tuple[float, float]:
    """The sparse MLP of one step: router and the routed experts' three
    matrices per token; the router's weights and as many experts as the
    step's tokens are expected to touch, read once a layer, and the
    activations in and out."""
    costs = model_costs(config, dtype_bytes)
    tokens = step["prefill_tokens"] + step["decodes"]
    h, e, k = config["hidden_size"], costs["experts"], costs["top_k"]
    touched = e * (1.0 - (1.0 - k / e) ** tokens)
    flops = 2.0 * costs["layers"] * tokens * (
        h * e + k * costs["expert_bytes"] / dtype_bytes)
    nbytes = costs["layers"] * (
        touched * costs["expert_bytes"]
        + dtype_bytes * (h * e + 2.0 * tokens * h))
    return flops, nbytes
