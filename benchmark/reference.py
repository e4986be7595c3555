"""Plain reference of the decoder the cells serve: a Qwen3-MoE style
causal language model (RMSNorm, GQA with per-head q/k RMSNorm, rotary
positions, softmax-top-k routed experts with renormalised weights, SwiGLU).

Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``: every expert computed
densely, no kernel, no cache, no batching.  It shares only the names of
the weight tree with the program; the sizes come from the configuration
file's published keys.

Departures from the published model: a text-only prompt gives the three
mrope position streams the same value, which makes mrope the plain rotary
embedding computed here (whether sections are interleaved or contiguous
then changes nothing).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

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


def logprobs(params, config: dict, token_ids: list[int],
             tp: int = 1) -> jax.Array:
    """Log-softmax over the vocabulary after every position of
    ``token_ids``: [T, vocab] float32.  ``params`` is the served weight
    tree (upcast here, layer by layer); ``config`` holds the published
    keys of the configuration file; ``tp`` its tensor-parallel degree."""
    hp = (config["head_dim"], config["rms_norm_eps"],
          float(config["rope_theta"]), config["num_experts_per_tok"],
          bool(config["norm_topk_prob"]), tp)
    ids = jnp.asarray(token_ids, jnp.int32)
    with jax.default_matmul_precision("highest"):
        x = params["embed"]["w"][ids].astype(jnp.float32)
        for layer in params["layers"]:
            x = _layer(layer, x, hp)
        return _head({k: params[k] for k in ("final_norm", "lm_head")}, x,
                     config["rms_norm_eps"])
