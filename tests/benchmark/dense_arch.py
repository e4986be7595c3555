"""A second architecture, written outside ``benchmark/``: a small dense
decoder (RMSNorm, GQA, plain rotary positions, one SwiGLU MLP a layer; no
q/k norm, no experts) under the contract of ``benchmark/archs/``.

``test_new_arch.py`` names it in a configuration file
(``"arch": "tests.benchmark.dense_arch"``) and serves, checks and counts
it through the harness as it stands: what a later ``model_config`` PR does
with a module of its own under ``benchmark/archs/``.  Nothing here is
shared with ``benchmark/archs/qwen3_moe.py``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


# ---------------------------------------------------------------- builder
def program_config(config: dict):
    from vllm_omni_tpu.models.common.transformer import TransformerConfig

    return TransformerConfig(
        vocab_size=config["vocab_size"],
        hidden_size=config["hidden_size"],
        num_layers=config["num_hidden_layers"],
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"],
        intermediate_size=config["intermediate_size"],
        rope_theta=float(config["rope_theta"]),
        rms_eps=config["rms_norm_eps"],
    )


def init_weights(key, cfg, dtype):
    from vllm_omni_tpu.models.common import transformer as tfm

    return tfm.init_params(key, cfg=cfg, dtype=dtype)


def param_specs(shapes):
    from vllm_omni_tpu.parallel.sharding import ar_param_specs_tree

    return ar_param_specs_tree(shapes)


# -------------------------------------------------------------- reference
def _norm(x, w, eps):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) * w


def _rotate(x, theta):
    """x [T, H, D]: the two halves of D turn by position x frequency."""
    t, _, d = x.shape
    freq = theta ** (-jnp.arange(d // 2, dtype=jnp.float32) / (d // 2))
    ang = jnp.arange(t, dtype=jnp.float32)[:, None, None] * freq
    a, b = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([a * jnp.cos(ang) - b * jnp.sin(ang),
                            b * jnp.cos(ang) + a * jnp.sin(ang)], -1)


def reference_logprobs(params, config: dict, token_ids: list[int],
                       last_n: int, tp: int = 1) -> jax.Array:
    if tp != 1:
        raise SystemExit("dense_arch: written for one chip")
    eps, d = config["rms_norm_eps"], config["head_dim"]
    f32 = lambda a: a.astype(jnp.float32)  # noqa: E731
    with jax.default_matmul_precision("highest"):
        x = f32(params["embed"]["w"])[jnp.asarray(token_ids)]
        t = x.shape[0]
        causal = jnp.tril(jnp.ones((t, t), bool))
        for layer in params["layers"]:
            h = _norm(x, f32(layer["input_norm"]["w"]), eps)
            q = _rotate((h @ f32(layer["q_proj"]["w"])).reshape(t, -1, d),
                        float(config["rope_theta"]))
            k = _rotate((h @ f32(layer["k_proj"]["w"])).reshape(t, -1, d),
                        float(config["rope_theta"]))
            v = (h @ f32(layer["v_proj"]["w"])).reshape(t, -1, d)
            rep = q.shape[1] // k.shape[1]
            k, v = jnp.repeat(k, rep, 1), jnp.repeat(v, rep, 1)
            s = jnp.einsum("qhd,khd->hqk", q, k) / d ** 0.5
            p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), -1)
            x = x + jnp.einsum("hqk,khd->qhd", p, v).reshape(t, -1) @ f32(
                layer["o_proj"]["w"])
            h = _norm(x, f32(layer["post_norm"]["w"]), eps)
            gate, up = jnp.split(h @ f32(layer["gate_up"]["w"]), 2, -1)
            x = x + (jax.nn.silu(gate) * up) @ f32(layer["down"]["w"])
        h = _norm(x[-last_n:], f32(params["final_norm"]["w"]), eps)
        return jax.nn.log_softmax(h @ f32(params["lm_head"]["w"]), -1)


# ----------------------------------------------------------------- counts
def _weights_per_layer(config: dict) -> tuple[int, int]:
    h, d = config["hidden_size"], config["head_dim"]
    q_dim = config["num_attention_heads"] * d
    kv_dim = config["num_key_value_heads"] * d
    return (2 * h * q_dim + 2 * h * kv_dim,
            3 * h * config["intermediate_size"])


def step_cost(step: dict, config: dict, mean_prefill_ctx: float,
              mean_decode_ctx: float, dtype_bytes: int = 2):
    """Every weight is read once a step and used by every token; attention
    pairs and the cache read take the contexts the caller gives."""
    tokens = step["prefill_tokens"] + step["decodes"]
    if tokens <= 0:
        return None
    layers = config["num_hidden_layers"]
    attn_w, mlp_w = _weights_per_layer(config)
    head_w = config["hidden_size"] * config["vocab_size"]
    q_dim = config["num_attention_heads"] * config["head_dim"]
    kv_dim = config["num_key_value_heads"] * config["head_dim"]
    pairs = (step["prefill_tokens"] * mean_prefill_ctx
             + step["decodes"] * mean_decode_ctx)
    flops = (2.0 * layers * (attn_w + mlp_w) * tokens
             + 4.0 * q_dim * layers * pairs
             + 2.0 * head_w * (step["prefills"] + step["decodes"]))
    kv_read = (step["decodes"] * mean_decode_ctx
               + step["prefills"] * mean_prefill_ctx)
    nbytes = dtype_bytes * (layers * (attn_w + mlp_w) + head_w
                            + 2 * layers * kv_dim * (kv_read + tokens))
    return flops, nbytes


def attn_cost(step: dict, config: dict, dtype_bytes: int = 2):
    """QK and PV over the attended pairs; every decode row reads its
    context, a prefill chunk the mean context of its tokens; queries in,
    outputs out."""
    layers = config["num_hidden_layers"]
    q_dim = config["num_attention_heads"] * config["head_dim"]
    kv_dim = config["num_key_value_heads"] * config["head_dim"]
    tokens = step["prefill_tokens"] + step["decodes"]
    kv_read = step["decode_ctx"]
    if step["prefill_tokens"] > 0:
        kv_read += step["prefills"] * step["prefill_ctx"] / step[
            "prefill_tokens"]
    return (4.0 * q_dim * layers * (step["prefill_ctx"] + step["decode_ctx"]),
            dtype_bytes * layers * (2.0 * kv_dim * kv_read
                                    + 2.0 * tokens * q_dim))


def moe_cost(step: dict, config: dict, dtype_bytes: int = 2):
    return None  # a dense decoder has no sparse MLP
