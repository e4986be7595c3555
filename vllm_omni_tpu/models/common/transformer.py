"""Generic Qwen-style causal transformer (GQA + RoPE + RMSNorm + SwiGLU).

One implementation serves every AR component in the framework: the
diffusion pipelines' text encoder (reference: Qwen2.5-VL encode_prompt,
pipeline_qwen_image.py:622-636), the Qwen3-Omni thinker/talker backbones
(reference: models/qwen3_omni/qwen3_moe.py — dense variant first, MoE via
``moe=True``), and the TTS LM.  Pure functions over a param pytree; both a
full-sequence forward (prefill / text encoding) and a paged-KV decode step
for the continuous-batching engine.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from vllm_omni_tpu.models.common import nn, state_layers
from vllm_omni_tpu.ops import (
    apply_partial_rope,
    apply_rope,
    apply_rope_ref,
    compute_mrope_freqs,
    compute_rope_freqs,
    flash_attention,
    paged_attention,
    ragged_paged_attention,
    rms_norm,
    silu_mul,
    write_kv_cache,
)
from vllm_omni_tpu.ops import indexer as indexer_ops
from vllm_omni_tpu.ops.activation import UNGATED
from vllm_omni_tpu.ops.paged_attention import heads_a_row, key_row_width

#: the kinds of layer whose sequences hold a state slot: what is
#: particular to each is its record of ``state_layers.KINDS``
STATE_KINDS = tuple(state_layers.KINDS)
#: the kind of a layer that is its feed-forward part ALONE (Nemotron-H's
#: ``E``): one norm, the sparse or dense MLP, the residual.  It has no
#: sequence mixer, so no weights of one, no pages and no state slot; and a
#: stack that has such layers is written ONE sublayer a layer, so its
#: mixer layers have no feed-forward part either (``one_sublayer``)
FEED_FORWARD = "feed_forward"


@dataclass(frozen=True)
class LatentKind:
    """One kind of latent-attention layer: its head count, its ranks, a
    head's widths and its rotary base.  A model whose layers are of two
    kinds (full and sliding-window) has two of these; the full kind's is
    read from ``TransformerConfig``'s own fields (``latent_kind``)."""

    num_heads: int
    q_lora_rank: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    rope_theta: float

    @property
    def row(self) -> int:
        """Numbers a cached token holds: the latent and the rotary key."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def scale(self) -> float:
        return (self.qk_nope_head_dim + self.qk_rope_head_dim) ** -0.5


@dataclass(frozen=True)
class HeadKind:
    """One kind of per-head attention layer, where a model's kinds differ
    in more than their window: its kv heads, its rotary base, and whether
    its softmax holds a learned sink a query head (a logit in the
    denominator with no value: ``ops/paged_attention.py sink_softmax``).
    A model with such layers has two of these; the full kind's is read
    from ``TransformerConfig``'s own fields (``head_kind``).  The heads'
    widths are the model's: ``head_dim`` a key, ``v_head_dim`` a value."""

    num_kv_heads: int
    rope_theta: float
    sink: bool = False


@dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int = 32000
    hidden_size: int = 1024
    num_layers: int = 8
    num_heads: int = 8
    num_kv_heads: int = 4
    head_dim: int = 128
    intermediate_size: int = 4096
    rope_theta: float = 1e6
    rms_eps: float = 1e-6
    # Multimodal 3D-RoPE: when set (3 splits of head_dim//2), positions are
    # [B, 3, S] (temporal/height/width streams, models/common/mrope.py)
    # instead of [B, S] (reference: OmniMRotaryEmbedding, mrope.py:25)
    mrope_sections: Optional[tuple[int, int, int]] = None
    qk_norm: bool = False  # per-head q/k RMSNorm (Qwen3 style)
    attention_bias: bool = False  # q/k/v projection biases (Qwen2 style)
    tie_word_embeddings: bool = False
    # Mixture-of-Experts (Qwen3-MoE style: softmax-topk router, normalized
    # gate weights; reference backbone models/qwen3_omni/qwen3_moe.py).
    # Expert weights are stacked on a leading E axis — shard it over the
    # mesh "ep" axis and GSPMD partitions the expert einsums (EP).
    # ``moe`` says that the stack HAS sparse layers: every layer from
    # ``first_k_dense_replace`` on.  What a layer's MLP is, the forward
    # reads from its weights (``"experts" in layer``), never from here
    moe: bool = False
    num_experts: int = 8
    num_experts_per_tok: int = 2
    moe_intermediate_size: int = 0  # 0 => intermediate_size
    # norm_topk_prob: Qwen3-MoE renormalizes the kept router weights;
    # the Qwen3-Omni talker keeps raw softmax mass (False)
    moe_renormalize: bool = True
    # always-on shared expert beside the routed ones, of this width (for
    # several shared experts, the sum of theirs); 0 => none.  With
    # ``shared_expert_gate`` it is combined through a learned per-token
    # sigmoid gate (Qwen2-MoE style: the Qwen3-Omni talker LM,
    # transformers Qwen3OmniMoeTalkerTextSparseMoeBlock; Qwen3-Next);
    # without, it is added whole (the DeepSeek-V3 family, GLM-4.7-Flash).
    # The forward applies the gate where ``shared_gate`` is in the layer
    shared_expert_size: int = 0
    shared_expert_gate: bool = True
    # leading layers whose MLP is a dense SwiGLU of ``intermediate_size``
    # in a stack whose other layers are sparse (first_k_dense_replace)
    first_k_dense_replace: int = 0
    # the router's scoring, as ``ops.moe.router_topk`` takes it: "softmax"
    # over the experts, or independent "sigmoid" scores (noaux_tc), with
    # ``router_bias`` a learned correction (``e_score_correction_bias``)
    # added for the selection alone, and the kept weights multiplied by
    # ``routed_scaling_factor`` after their renormalisation
    router_scoring: str = "softmax"
    router_bias: bool = False
    routed_scaling_factor: float = 1.0
    # "routed" (grouped-matmul top-k dispatch; EP over the mesh "ep" axis
    # when ops.moe.set_ep_mesh was called) | "dense" (oracle: all experts
    # compute all tokens)
    moe_dispatch: str = "routed"
    # the MLPs (dense, routed experts, shared expert alike): gated
    # (``gate_up`` [.., 2 I]: ``act(gate) * up``, silu the one built) or
    # ungated (``up`` [.., I]: ``down(act(up(x)))``, Nemotron-H's relu2).
    # The forward reads which from the weights (``"gate_up"`` or ``"up"``)
    mlp_gated: bool = True
    mlp_activation: str = "silu"
    # latent experts (> 0; Nemotron-H's ``moe_latent_size``): the routed
    # experts read and write rows of this width, between a projection
    # hidden -> latent before the dispatch and one latent -> hidden after
    # the combine (``fc1_latent_proj`` / ``fc2_latent_proj``); the router
    # and the shared expert still read the model's own row
    moe_latent_size: int = 0
    # Tensor parallelism (Megatron col/row sharding over a mesh axis).
    # When set, the forward runs INSIDE shard_map over this axis with
    # per-shard weights (heads and MLP columns divided): psum after
    # o_proj/down restores full activations, all_gather reassembles
    # vocab-sharded logits.  None => single-shard semantics, no
    # collectives (reference: tensor_parallel_size in stage YAML,
    # model_executor/stage_configs/qwen3_omni_moe.yaml:27).
    tp_axis: Optional[str] = None
    # ---- hybrid stacks (Qwen3-Next): layer i is softmax attention iff
    # (i + 1) % full_attention_interval == 0, every other one a Gated
    # DeltaNet linear-attention layer (ops/gated_delta_rule.py) that
    # carries a per-sequence recurrent state instead of KV pages.
    # 0 => every layer is softmax attention
    full_attention_interval: int = 0
    linear_num_key_heads: int = 0
    linear_num_value_heads: int = 0
    linear_key_head_dim: int = 0
    linear_value_head_dim: int = 0
    linear_conv_kernel_dim: int = 4
    # gated attention: q_proj is twice as wide, a head's columns are
    # [q | gate], and the attention output is scaled by sigmoid(gate)
    attn_output_gate: bool = False
    # rotary embedding on the first head_dim * factor dims of a head only
    partial_rotary_factor: float = 1.0
    # RMSNorm weights stored zero-centred and applied as (1 + w) in
    # float32 (the GDN layer's gated output norm keeps a plain weight)
    norm_zero_centered: bool = False
    # the chip's share of an expert-parallel deployment (ops/moe.py
    # routed_moe_share): the router keeps its published width
    # ``router_experts`` (0 => num_experts) while ``num_experts`` counts
    # the experts HELD here, ids expert_offset .. expert_offset +
    # num_experts; picks that live elsewhere add nothing here
    router_experts: int = 0
    expert_offset: int = 0
    # ---- multi-head latent attention (kv_lora_rank > 0: every attention
    # layer; DeepSeek-V2's MLA as GLM-4.7-Flash has it).  Queries come
    # through a low-rank pair (q_lora_rank) as [q_nope | q_rope] a head;
    # keys and values through one latent row a token, [c_kv | k_rope] of
    # kv_lora_rank + qk_rope_head_dim numbers, which is ALL the cache
    # holds (``kv_layout``); ``kv_b`` expands a latent row to a head's
    # [k_nope | v].  Served absorbed: kv_b's two halves are applied to the
    # query and to the attention output instead of to every cached row
    # (``_mla_mixer``).  ``head_dim`` is then qk_nope + qk_rope (the
    # softmax scale), ``num_kv_heads`` the published count, unused
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    # keep the residual stream in float32 between layers: the mixers' and
    # MLPs' out projections leave their matmuls in float32 and are added
    # unrounded; norm outputs are cast to the weights' dtype, so every
    # matmul still takes bfloat16 operands.  For stacks that amplify what
    # they are fed: a Gated DeltaNet layer's output is normalised to unit
    # size a head, so it is most of what the layer adds to the stream, and
    # with random weights a perturbation grows 1.4-2.2x through each such
    # layer (29x through 8 layers, measured).  With a bfloat16 stream
    # Qwen3-Next's served logprobs read 0.09-0.14 from the float32
    # reference, against the benchmark's limit of 0.15; with this,
    # 0.03-0.13 (PERF.md, PR 28)
    residual_in_fp32: bool = False
    # ---- layers of several kinds, as data: one of "full_attention",
    # "sliding_attention", "linear_attention", "conv", "mamba",
    # "feed_forward" a layer (the published ``layer_types``, or the
    # letters of a ``hybrid_override_pattern``); None => derived from
    # ``full_attention_interval``
    layer_kinds: Optional[tuple[str, ...]] = None
    # a "conv" layer is a gated short convolution (LFM2): ``[B | C | u] =
    # x W_in``, a depth-wise causal convolution of ``shortconv_kernel``
    # taps over ``B * u`` (no bias, no activation), gated by ``C``, then
    # ``W_out``.  What a sequence carries through it is the last
    # ``shortconv_kernel - 1`` rows of ``B * u`` and nothing else: a state
    # slot of one array (docs/kv_cache.md, "Three kinds of state")
    shortconv_kernel: int = 3
    # a "sliding_attention" layer attends the last ``sliding_window`` keys,
    # the token itself among them; its rows live in pages of a second kind
    # that are given back once they slide out of reach (docs/kv_cache.md).
    # In a model with latent attention it is latent attention with widths
    # of its own (``sliding``); in any other it is the model's per-head
    # attention, and its pages hold (K, V) rows as the full layers' do
    sliding: Optional[LatentKind] = None
    sliding_window: int = 0
    # ... and where the sliding layers of a model with PER-HEAD attention
    # differ from its full layers in their kv heads, their rotary base or
    # a sink, the sliding kind's record (None: they differ in the window
    # alone).  A per-head value narrower than the key is ``v_head_dim``
    # (0: the key's ``head_dim``; the latent fields above give it its
    # meaning for a latent model), and ``attention_value_scale``
    # multiplies every value before it is cached and attended (at 1.0 no
    # operation is emitted)
    sliding_heads: Optional[HeadKind] = None
    attention_value_scale: float = 1.0
    # learned sparse attention on the full layers (index_topk > 0): an
    # indexer of ``index_n_heads`` heads of ``index_head_dim`` scores every
    # cached token (one index key a token, in a pool beside the latent
    # rows) and the ``index_topk`` best are attended (ops/indexer.py)
    index_n_heads: int = 0
    index_head_dim: int = 0
    index_topk: int = 0
    # the latents are multiplied by sqrt(hidden / rank) after their norms
    # (scale-corrected latent attention)
    mla_lora_rescale: bool = False
    # one sigmoid gate a head on the attention output, from the layer's
    # normed input (``attn_output_gate`` is the element-wise one)
    attn_head_gate: bool = False
    # a "mamba" layer is Mamba-2 (Granite 4.0-H): ``[z | xBC | dt] = x
    # W_in``, a depth-wise causal convolution of ``mamba_conv_kernel`` taps
    # with a bias over ``xBC`` and a silu, then the state-space rule over
    # ``mamba_num_heads`` heads of ``mamba_head_dim`` with
    # ``mamba_n_groups`` groups of B and C of ``mamba_state_dim`` (head h
    # reads group h // (heads / groups); ops/ssd.py), the gate ``silu(z)``,
    # an RMSNorm a group's channels at a time and ``W_out``.  A sequence
    # carries the convolution's last inputs and a ``[heads, head_dim,
    # state_dim]`` float32 state, which the rule updates where it lies in
    # the slots (docs/kv_cache.md, "Four kinds of state")
    mamba_num_heads: int = 0
    mamba_head_dim: int = 0
    mamba_state_dim: int = 0
    mamba_conv_kernel: int = 4
    mamba_chunk_size: int = 256
    mamba_n_groups: int = 1
    # attention without positions (``position_embedding_type`` "nope"):
    # no table is built and neither queries nor keys are rotated
    use_rope: bool = True
    # the softmax scale of the per-head attention layers where it is not
    # ``head_dim ** -0.5`` (None); the kernels take it as ``scale``
    attention_multiplier: Optional[float] = None
    # the Granite family's multipliers: the embedding's rows are
    # multiplied by the first, every branch by the second before it joins
    # the residual stream, the logits DIVIDED by the third.  At 1.0 no
    # operation is emitted
    embedding_multiplier: float = 1.0
    residual_multiplier: float = 1.0
    logits_scaling: float = 1.0
    # ---- the Cohere family's block (Command A+).  ONE norm a layer, read
    # by the mixer and by the MLP, whose branches join the stream in one
    # sum: ``x + mixer(n(x)) + mlp(n(x))``.  Such a layer's weights hold
    # no ``post_norm``; the initialiser and ``_layer_step`` read this
    parallel_block: bool = False
    # the stack's norm (every layer's and the final one) is a LayerNorm:
    # the row's mean subtracted, then its variance's rsqrt, a weight and
    # no bias, ``rms_eps`` its epsilon
    norm_subtract_mean: bool = False
    # the kinds of layer whose queries and keys are rotated, where not
    # every kind is (None): a model that rotates its sliding layers and
    # leaves its full layers without positions names the one.  A rotary
    # embedding that pairs NEIGHBOURING dims (``rope_gptj``) is served by
    # the half-split rotation over q and k columns permuted once in the
    # weight tree (``ops/rope.py pairs_to_halves``)
    rope_kinds: Optional[tuple[str, ...]] = None
    # the shared expert's output times this before it is added: the MEAN
    # of n shared experts is one shared expert of n times the width at
    # 1 / n.  At 1.0 no operation is emitted
    shared_expert_scale: float = 1.0

    @property
    def layer_types(self) -> tuple[str, ...]:
        if self.layer_kinds is not None:
            return self.layer_kinds
        n = self.full_attention_interval
        return tuple(
            "linear_attention" if n and (i + 1) % n else "full_attention"
            for i in range(self.num_layers))

    @property
    def one_sublayer(self) -> bool:
        """Whether a layer is ONE sublayer, a mixer or a feed-forward part
        (the stack has ``FEED_FORWARD`` layers), and not a mixer and then a
        feed-forward part."""
        return FEED_FORWARD in self.layer_types

    @property
    def kv_layer_types(self) -> tuple[str, ...]:
        """The kinds of the layers that hold KV pages, in layer order: the
        attention ones, neither a state kind nor a layer without a mixer."""
        return tuple(kind for kind in self.layer_types
                     if kind not in STATE_KINDS and kind != FEED_FORWARD)

    @property
    def num_kv_layers(self) -> int:
        """Layers that hold KV pages (the softmax-attention ones)."""
        return len(self.kv_layer_types)

    @property
    def num_window_layers(self) -> int:
        """Of those, the ones whose pages are of the window kind."""
        return self.layer_types.count("sliding_attention")

    @property
    def num_state_layers(self) -> int:
        """Layers that hold a per-sequence state in a slot, not pages."""
        return sum(kind in STATE_KINDS for kind in self.layer_types)

    def state_shapes(self, kind: str) -> tuple[tuple[int, ...], ...]:
        """What one sequence carries through a layer of ``kind`` (one of
        ``STATE_KINDS``), an array a shape."""
        return state_layers.KINDS[kind].shapes(self)

    def latent_kind(self, kind: str = "full_attention") -> LatentKind:
        """The latent widths of a kind of layer."""
        if kind == "sliding_attention":
            return self.sliding
        return LatentKind(self.num_heads, self.q_lora_rank,
                          self.kv_lora_rank, self.qk_nope_head_dim,
                          self.qk_rope_head_dim, self.v_head_dim,
                          self.rope_theta)

    def head_kind(self, kind: str = "full_attention") -> HeadKind:
        """The kv heads, rotary base and sink of a kind of per-head
        attention layer."""
        if kind == "sliding_attention" and self.sliding_heads is not None:
            return self.sliding_heads
        return HeadKind(self.num_kv_heads, self.rope_theta)

    @property
    def value_dim(self) -> int:
        """A per-head value's width."""
        return self.v_head_dim or self.head_dim

    @property
    def rotary_dim(self) -> int:
        if self.kv_lora_rank:
            return self.qk_rope_head_dim
        return int(self.head_dim * self.partial_rotary_factor)

    def kv_layout_of(self, kind: str = "full_attention") -> "KVLayout":
        """What the paged cache of one attention layer of this kind holds
        a token."""
        if self.kv_lora_rank:
            row = self.latent_kind(kind).row
            return KVLayout("latent", 1, -(-row // 128) * 128, 1, row)
        # heads narrower than a 128-lane row lie side by side in one
        # (ops/paged_attention.py heads_a_row): 8 kv heads of 64 are 4
        # rows of 128, no lane of which is padding
        kv_heads = self.head_kind(kind).num_kv_heads
        if self.value_dim != self.head_dim:
            # a key wider than its value: a K pool and a V pool of
            # different row widths, a key of one and a half lane tiles in
            # two (``key_row_width``: 192 in 256, 64 lanes of zeros)
            return KVLayout("kv", kv_heads, key_row_width(self.head_dim),
                            2, self.head_dim, self.value_dim)
        pack = heads_a_row(self.head_dim, kv_heads)
        return KVLayout("kv", kv_heads // pack,
                        self.head_dim * pack, 2, self.head_dim * pack)

    @property
    def kv_layout(self) -> "KVLayout":
        """The full kind's (the only kind, but for a model with
        sliding-window layers)."""
        return self.kv_layout_of("full_attention")

    def attn_kwargs_of(self, kind: str = "full_attention") -> dict:
        """What the paged attention ops are told beyond their operands:
        nothing for per-head K and V pages (the scale is the head's, or
        the model's ``attention_multiplier`` where it states one); for
        a latent pool the scale of the un-absorbed head and the lanes of
        a row that are the value; for a sliding-window layer of either
        layout its window."""
        if not self.kv_lora_rank:
            out = ({} if self.attention_multiplier is None
                   else {"scale": self.attention_multiplier})
        else:
            lk = self.latent_kind(kind)
            out = {"scale": lk.scale, "v_dim": lk.kv_lora_rank}
        if kind == "sliding_attention":
            out["window"] = self.sliding_window
        return out

    @property
    def attn_kwargs(self) -> dict:
        return self.attn_kwargs_of("full_attention")

    @property
    def linear_conv_dim(self) -> int:
        return (2 * self.linear_num_key_heads * self.linear_key_head_dim
                + self.linear_num_value_heads * self.linear_value_head_dim)

    @property
    def mamba_dims(self) -> tuple[int, int, int]:
        """(heads, a head's width, the state's width) of a Mamba-2 layer."""
        return (self.mamba_num_heads, self.mamba_head_dim,
                self.mamba_state_dim)

    @property
    def mamba_conv_dim(self) -> int:
        """Channels of a Mamba-2 layer's convolution: u, then B and C a
        group."""
        return (self.mamba_num_heads * self.mamba_head_dim
                + 2 * self.mamba_n_groups * self.mamba_state_dim)

    @property
    def gdn_heads(self) -> tuple[int, int, int, int]:
        return (self.linear_num_key_heads, self.linear_num_value_heads,
                self.linear_key_head_dim, self.linear_value_head_dim)

    @staticmethod
    def tiny(vocab_size: int = 128) -> "TransformerConfig":
        return TransformerConfig(
            vocab_size=vocab_size,
            hidden_size=64,
            num_layers=2,
            num_heads=4,
            num_kv_heads=2,
            head_dim=16,
            intermediate_size=128,
        )

    @staticmethod
    def tiny_moe(vocab_size: int = 128) -> "TransformerConfig":
        return TransformerConfig(
            vocab_size=vocab_size,
            hidden_size=64,
            num_layers=2,
            num_heads=4,
            num_kv_heads=2,
            head_dim=16,
            intermediate_size=128,
            moe=True,
            num_experts=4,
            num_experts_per_tok=2,
            moe_intermediate_size=64,
        )


class KVLayout(NamedTuple):
    """The paged cache of one attention layer, as ``init_kv_cache``
    allocates it and the engine accounts it (docs/kv_cache.md): ``pools``
    arrays of ``[heads, pages, page_size, width]``."""

    kind: str    # "kv": a K and a V pool of per-head rows | "latent"
    heads: int   # rows a token has in a pool: its kv heads, or at a head
    #              of 64 half as many (two heads to a 128-lane row)
    width: int   # lanes a row takes, declared padding included
    pools: int   # 2 (K, V) | 1 (the row is key and value at once)
    used: int    # lanes of a row that hold numbers (width - padding)
    # lanes of a V pool's row where it is not as wide as the K pool's
    # (0: ``width``, as every model's but one whose key is wider than its
    # value); a V row holds no padding
    v_width: int = 0

    @property
    def value_width(self) -> int:
        """Lanes a V pool's row takes (a shared pool has none)."""
        return (self.v_width or self.width) if self.pools == 2 else 0

    @property
    def lanes(self) -> int:
        """Lanes a cached token takes a head in this layer's pools
        together, padding included."""
        return self.width + self.value_width

    @property
    def lanes_used(self) -> int:
        """Of those, the lanes that hold numbers."""
        return self.used + (self.value_width
                            and (self.v_width or self.used))


def init_params(key, cfg: TransformerConfig, dtype=jnp.float32):
    keys = jax.random.split(key, cfg.num_layers + 3)
    params = {
        "embed": nn.embedding_init(keys[0], cfg.vocab_size, cfg.hidden_size, dtype),
        "layers": [],
    }
    if not cfg.tie_word_embeddings:
        params["lm_head"] = nn.linear_init(
            keys[1], cfg.hidden_size, cfg.vocab_size, bias=False, dtype=dtype
        )

    def norm_init(key, dim):
        if not cfg.norm_zero_centered:
            return nn.rmsnorm_init(dim, dtype)
        # stored zero-centred and applied as (1 + w); the published
        # initialiser writes zeros, a little noise here keeps the (1 + w)
        # visible to every comparison with the reference
        return {"w": jax.random.uniform(key, (dim,), dtype,
                                        minval=-0.1, maxval=0.1)}

    params["final_norm"] = norm_init(keys[2], cfg.hidden_size)
    for i, kind in enumerate(cfg.layer_types):
        k = jax.random.split(keys[i + 3], 8)
        kn = jax.random.split(k[4], 4)
        # a layer is its mixer's half (``input_norm`` and the mixer) and
        # its feed-forward half (``post_norm`` and the MLP).  In a stack of
        # one sublayer a layer it holds ONE of the two, and what it holds
        # is what ``_layer_step`` runs: nothing stands in for the other
        layer = {}
        if kind != FEED_FORWARD:
            layer["input_norm"] = norm_init(kn[0], cfg.hidden_size)
            layer.update(_mixer_init(k, kn, cfg, kind, norm_init, dtype))
        if kind == FEED_FORWARD or not cfg.one_sublayer:
            if not cfg.parallel_block:  # else ``input_norm`` feeds both
                layer["post_norm"] = norm_init(kn[1], cfg.hidden_size)
            layer.update(_mlp_init(
                k, cfg, cfg.moe and i >= cfg.first_k_dense_replace, dtype))
        params["layers"].append(layer)
    return params


def _mixer_init(k, kn, cfg: TransformerConfig, kind: str, norm_init, dtype):
    """The sequence mixer's weights of a layer of ``kind``, from the
    layer's eight keys ``k`` and its norms' four ``kn``."""
    record = state_layers.KINDS.get(kind)
    if record:
        return {record.key: record.init(k, cfg, dtype)}
    if cfg.kv_lora_rank:
        lk = cfg.latent_kind(kind)
        mixer = {"mla": _mla_init(k, cfg, lk, dtype),
                 "o_proj": nn.linear_init(
                     k[3], lk.num_heads * lk.v_head_dim, cfg.hidden_size,
                     bias=False, dtype=dtype)}
        if cfg.attn_head_gate:
            mixer["attn_gate"] = nn.linear_init(
                jax.random.fold_in(k[3], 1), cfg.hidden_size,
                lk.num_heads, bias=False, dtype=dtype)
        if cfg.index_topk and kind == "full_attention":
            mixer["indexer"] = _indexer_init(
                jax.random.fold_in(k[3], 2), cfg, lk, dtype)
        return mixer
    hk = cfg.head_kind(kind)
    q_dim = cfg.num_heads * cfg.head_dim
    kv_dim = hk.num_kv_heads * cfg.head_dim
    v_dim = hk.num_kv_heads * cfg.value_dim
    o_dim = cfg.num_heads * cfg.value_dim
    qkv_bias = cfg.attention_bias
    # gated attention doubles q_proj: a head's columns are [q | gate]
    q_out = q_dim * (2 if cfg.attn_output_gate else 1)
    mixer = {
        "q_proj": nn.linear_init(k[0], cfg.hidden_size, q_out, bias=qkv_bias, dtype=dtype),
        "k_proj": nn.linear_init(k[1], cfg.hidden_size, kv_dim, bias=qkv_bias, dtype=dtype),
        "v_proj": nn.linear_init(k[2], cfg.hidden_size, v_dim, bias=qkv_bias, dtype=dtype),
        "o_proj": nn.linear_init(k[3], o_dim, cfg.hidden_size, bias=False, dtype=dtype),
    }
    if hk.sink:
        # one learned logit a query head, float32 as the softmax that
        # reads it.  A trained sink holds a fair part of a row's mass;
        # the draw is wide enough that its presence shows
        mixer["sinks"] = jax.random.uniform(
            jax.random.fold_in(k[3], 3), (cfg.num_heads,), jnp.float32,
            minval=-1.0, maxval=1.0)
    if cfg.qk_norm and kind == "full_attention":
        mixer["q_norm"] = norm_init(kn[2], cfg.head_dim)
        mixer["k_norm"] = norm_init(kn[3], cfg.head_dim)
    return mixer


def _mlp_init(k, cfg: TransformerConfig, sparse: bool, dtype):
    """The feed-forward part's weights of a layer, from the layer's eight
    keys: the router, the stacked experts and the shared expert where the
    layer is ``sparse``, a dense MLP of ``intermediate_size`` where it is
    not.  Gated MLPs keep gate and up fused (``gate_up``), ungated ones
    have ``up`` alone: the forward reads which from the name."""
    up_name, up_cols = ("gate_up", 2) if cfg.mlp_gated else ("up", 1)
    if not sparse:
        return {
            up_name: nn.linear_init(
                k[4], cfg.hidden_size, up_cols * cfg.intermediate_size,
                bias=False, dtype=dtype),
            "down": nn.linear_init(
                k[5], cfg.intermediate_size, cfg.hidden_size, bias=False,
                dtype=dtype),
        }
    e = cfg.num_experts
    inter = cfg.moe_intermediate_size or cfg.intermediate_size
    # what an expert reads and writes: the model's row, or the latent's
    # between its two projections
    width = cfg.moe_latent_size or cfg.hidden_size
    scale_in = 1.0 / (width ** 0.5)
    scale_out = 1.0 / (inter ** 0.5)
    k6, k7 = jax.random.split(k[6])
    mlp = {"router": nn.linear_init(
        k[5], cfg.hidden_size, cfg.router_experts or e, bias=False,
        dtype=dtype)}
    if cfg.router_bias:
        # the learned correction of the selection; float32 as the
        # checkpoint keeps it.  The published initialiser writes zeros: a
        # little noise here keeps "selected on s + b, weighted by s"
        # visible to every comparison
        mlp["router"]["e_bias"] = jax.random.uniform(
            jax.random.fold_in(k[5], 1), (cfg.router_experts or e,),
            jnp.float32, minval=-0.05, maxval=0.05)
    # stacked expert weights: leading E axis is the EP shard axis
    mlp["experts"] = {
        up_name: jax.random.uniform(
            k6, (e, width, up_cols * inter), dtype,
            minval=-scale_in, maxval=scale_in),
        "down": jax.random.uniform(
            k7, (e, inter, width), dtype,
            minval=-scale_out, maxval=scale_out),
    }
    if cfg.moe_latent_size:
        # the checkpoint's names without their suffix
        mlp["fc1_latent_proj"] = nn.linear_init(
            jax.random.fold_in(k[6], 1), cfg.hidden_size, width,
            bias=False, dtype=dtype)
        mlp["fc2_latent_proj"] = nn.linear_init(
            jax.random.fold_in(k[6], 2), width, cfg.hidden_size,
            bias=False, dtype=dtype)
    if cfg.shared_expert_size:
        ks1, ks2, ks3 = jax.random.split(k[7], 3)
        mlp["shared_expert"] = {
            up_name: nn.linear_init(
                ks1, cfg.hidden_size, up_cols * cfg.shared_expert_size,
                bias=False, dtype=dtype),
            "down": nn.linear_init(
                ks2, cfg.shared_expert_size, cfg.hidden_size,
                bias=False, dtype=dtype),
        }
        if cfg.shared_expert_gate:
            mlp["shared_gate"] = nn.linear_init(
                ks3, cfg.hidden_size, 1, bias=False, dtype=dtype)
    return mlp


def _mla_init(k, cfg: TransformerConfig, lk: LatentKind, dtype):
    """One latent-attention mixer's weights beside ``o_proj``, at the
    widths of the layer's kind, under the checkpoint's names without their
    suffixes (``q_a_proj``, ``q_a_layernorm``, ``q_b_proj``,
    ``kv_a_proj_with_mqa``, ``kv_a_layernorm``, ``kv_b_proj``).  ``kv_b``
    stays one matrix [kv_lora_rank, H * (qk_nope + v)], a head's columns
    [k_nope | v]; the forward cuts it into the two halves it absorbs."""
    if not lk.q_lora_rank:
        raise ValueError("latent attention without q_lora_rank (a plain "
                         "q_proj) is not built")
    h, r = cfg.hidden_size, lk.kv_lora_rank
    qk = lk.qk_nope_head_dim + lk.qk_rope_head_dim
    return {
        "q_a": nn.linear_init(k[0], h, lk.q_lora_rank, bias=False,
                              dtype=dtype),
        "q_a_norm": nn.rmsnorm_init(lk.q_lora_rank, dtype),
        "q_b": nn.linear_init(k[1], lk.q_lora_rank, lk.num_heads * qk,
                              bias=False, dtype=dtype),
        "kv_a": nn.linear_init(k[2], h, r + lk.qk_rope_head_dim,
                               bias=False, dtype=dtype),
        "kv_a_norm": nn.rmsnorm_init(r, dtype),
        "kv_b": nn.linear_init(
            k[5], r, lk.num_heads * (lk.qk_nope_head_dim + lk.v_head_dim),
            bias=False, dtype=dtype),
    }


def _indexer_init(key, cfg: TransformerConfig, lk: LatentKind, dtype):
    """The indexer of a full layer (``wq_b``, ``wk``, ``k_norm``,
    ``weights_proj`` of the DeepSeek-V3.2 checkpoint): queries from the
    layer's query latent, one key a token from its normed input through a
    LayerNorm (weight and bias), a weight a head from the same input."""
    kq, kk, kw, kb = jax.random.split(key, 4)
    n, d = cfg.index_n_heads, cfg.index_head_dim
    return {
        "wq": nn.linear_init(kq, lk.q_lora_rank, n * d, bias=False,
                             dtype=dtype),
        "wk": nn.linear_init(kk, cfg.hidden_size, d, bias=False,
                             dtype=dtype),
        # the published initialiser writes ones and zeros: a little noise
        # keeps weight and bias visible to every comparison
        "k_norm": {"w": (1.0 + jax.random.uniform(
            kb, (d,), jnp.float32, minval=-0.1, maxval=0.1)).astype(dtype),
                   "b": jax.random.uniform(
            jax.random.fold_in(kb, 1), (d,), jnp.float32, minval=-0.1,
            maxval=0.1).astype(dtype)},
        "w": nn.linear_init(kw, cfg.hidden_size, n, bias=False,
                            dtype=dtype),
    }


def _qkv(layer, cfg: TransformerConfig, x):
    """x: [T, hidden] -> q [T, H, D], k/v [T, Hkv, D] with RoPE-ready
    layout.  Head counts derive from the weights, not the config: under
    tensor parallelism each shard carries num_heads/tp heads."""
    t = x.shape[0]
    gate = None
    if cfg.attn_output_gate:
        # a head's columns are [q | gate]; the gate scales the attention
        # output after the kernel (sigmoid, _layer_step)
        qg = nn.linear(layer["q_proj"], x).reshape(t, -1, 2 * cfg.head_dim)
        q, gate = qg[..., :cfg.head_dim], qg[..., cfg.head_dim:]
    else:
        q = nn.linear(layer["q_proj"], x).reshape(t, -1, cfg.head_dim)
    k = nn.linear(layer["k_proj"], x).reshape(t, -1, cfg.head_dim)
    v = nn.linear(layer["v_proj"], x).reshape(t, -1, cfg.value_dim)
    if cfg.attention_value_scale != 1.0:
        v = (v.astype(jnp.float32) * cfg.attention_value_scale).astype(
            v.dtype)
    if cfg.qk_norm:
        q = _rms(cfg, q, layer["q_norm"])
        k = _rms(cfg, k, layer["k_norm"])
    return q, k, v, gate


def _rms(cfg: TransformerConfig, x, norm):
    """The stack's norm.  RMSNorm with the model's weight convention:
    plain ``w``, or ``(1 + w)`` in float32 where the weights are stored
    zero-centred; or, where the configuration says so
    (``norm_subtract_mean``), a LayerNorm with a weight and no bias, its
    mean and variance over the row in float32."""
    w = norm["w"]
    if cfg.norm_subtract_mean:
        xc = x.astype(jnp.float32)
        xc = xc - jnp.mean(xc, axis=-1, keepdims=True)
        var = jnp.mean(xc * xc, axis=-1, keepdims=True)
        return (xc * jax.lax.rsqrt(var + cfg.rms_eps)
                * w.astype(jnp.float32)).astype(x.dtype)
    if cfg.norm_zero_centered:
        w = 1.0 + w.astype(jnp.float32)
    return rms_norm(x, w, cfg.rms_eps)


def _ungated_act(weights, cfg: TransformerConfig):
    """None for a gated MLP (its weights hold ``gate_up``: ``silu(gate) *
    up``, the one gated form built); the activation of an ungated one
    (``up`` alone), by the configuration's name for it."""
    if "gate_up" in weights:
        if cfg.mlp_activation != "silu":
            raise NotImplementedError(
                f"a gated MLP with {cfg.mlp_activation!r}: only silu")
        return None
    return UNGATED[cfg.mlp_activation]


def _dense_mlp(weights, cfg: TransformerConfig, x):
    """One dense MLP (a layer's own, or a shared expert): ``down(silu(gate)
    * up)`` where it is gated, ``down(act(up(x)))`` where it is not."""
    act = _ungated_act(weights, cfg)
    if act is None:
        return nn.linear(weights["down"],
                         silu_mul(nn.linear(weights["gate_up"], x)))
    return nn.linear(weights["down"], act(nn.linear(weights["up"], x)))


def _moe_mlp_dense(layer, cfg: TransformerConfig, x, rows=None):
    """Dense-dispatch MoE oracle: every expert computes every token,
    combined with the (renormalized) top-k router weights as a [T, E]
    mask.  Kept as the numerics oracle for the routed path (and the
    GSPMD fallback when neither routing mode applies); a k/E FLOP waste
    at real geometries (VERDICT r1 weak#4).  The router reads ``x``, the
    experts take ``rows`` (None: ``x`` itself; else its latent)."""
    from vllm_omni_tpu.ops import moe as moe_ops

    t = x.shape[0]
    rows = x if rows is None else rows
    experts = layer["experts"]
    act = _ungated_act(experts, cfg)
    topk_idx, topk_w = moe_ops.router_topk(
        x, layer["router"]["w"], cfg.num_experts_per_tok,
        cfg.moe_renormalize, **_router_kwargs(layer, cfg))
    # [T, E] combine weights (zero for non-selected experts)
    combine = jnp.zeros((t, layer["router"]["w"].shape[-1]),
                        jnp.float32).at[
        jnp.arange(t)[:, None], topk_idx
    ].set(topk_w)
    held = experts["down"].shape[0]
    if held != combine.shape[-1]:  # an expert share: the held columns only
        combine = jax.lax.dynamic_slice_in_dim(
            combine, cfg.expert_offset, held, axis=1)
    h = jnp.einsum("th,ehf->etf", rows,
                   experts["gate_up" if act is None else "up"])
    h = silu_mul(h) if act is None else act(h)
    y = jnp.einsum("etf,efh->eth", h, experts["down"])
    return jnp.einsum("eth,te->th", y, combine.astype(x.dtype))


def _router_kwargs(layer, cfg: TransformerConfig) -> dict:
    """The router's scoring as ``ops.moe.router_topk`` takes it; {} for
    the plain softmax router.  A sigmoid router's logits stay in float32,
    as the published implementation computes them."""
    if cfg.router_scoring == "softmax" and "e_bias" not in layer["router"] \
            and cfg.routed_scaling_factor == 1.0:
        return {}
    return {"scoring": cfg.router_scoring,
            "bias": layer["router"].get("e_bias"),
            "scale": cfg.routed_scaling_factor,
            "float32_logits": cfg.router_scoring == "sigmoid"}


def _moe_mlp(layer, cfg: TransformerConfig, x):
    """Top-k MoE dispatch.  Default: routed grouped-matmul (ops/moe.py —
    FLOPs scale with top-k, not E), expert-parallel over the mesh ``ep``
    axis when one is registered via ``ops.moe.set_ep_mesh``.  The dense
    path (``cfg.moe_dispatch == "dense"``) is the test oracle.

    What the experts are, their weights say: gated SwiGLU (``gate_up``) or
    ungated (``up``, the configuration's activation).  Where the layer
    holds the two latent projections the experts work in the latent: the
    router and the shared expert read the model's row ``x``, the dispatch
    takes ``x W_1`` and its result goes back through ``W_2`` (both under
    ``omni.moe_latent``).  On an expert share ``W_2`` is applied to what
    the held experts gave: it is linear and has no bias, so the shares of
    a deployment still add up to the layer."""
    lead = x.shape[:-1]
    x = x.reshape(-1, x.shape[-1])
    experts = layer["experts"]
    up = experts.get("gate_up", experts.get("up"))
    # what the dispatch is told beyond the gated, full-width default: {}
    # for every model that has neither ungated nor latent experts
    form = {}
    if "gate_up" not in experts:
        form["act"] = _ungated_act(experts, cfg)
    rows = x
    if "fc1_latent_proj" in layer:
        with jax.named_scope("omni.moe_latent"):
            rows = nn.linear(layer["fc1_latent_proj"], x)
        form["route_on"] = x
    if cfg.moe_dispatch == "dense":
        out = _moe_mlp_dense(layer, cfg, x, rows)
    else:
        from vllm_omni_tpu.ops import moe as moe_ops

        mesh = moe_ops.ep_mesh()
        router = _router_kwargs(layer, cfg)
        shared_out = layer["router"]["w"].shape[-1] != up.shape[0]
        if mesh is not None and form:
            raise NotImplementedError(
                "ungated or latent experts over an ep mesh are not built "
                "(the exchange at the latent's width); one chip serves "
                "them whole or as an expert share")
        if mesh is not None and cfg.moe_dispatch == "a2a":
            if router:
                raise NotImplementedError(
                    "the all-to-all expert-parallel dispatch takes the "
                    "plain softmax router; a sigmoid / correction-bias / "
                    "scaled router rides the psum dispatch (moe_dispatch "
                    "'routed') or an expert share")
            out = moe_ops.routed_moe_ep_a2a(
                x, layer["router"]["w"], up, experts["down"],
                cfg.num_experts_per_tok, mesh,
                renormalize=cfg.moe_renormalize,
            )
        elif mesh is not None:
            # the shards route as routed_moe does (the activations'
            # dtype) unless the router says otherwise
            out = moe_ops.routed_moe_ep(
                x, layer["router"]["w"], up, experts["down"],
                cfg.num_experts_per_tok, mesh,
                renormalize=cfg.moe_renormalize, **router,
            )
        elif shared_out:
            # the chip's share of an expert-parallel deployment: route
            # over the router's whole width, compute what the experts
            # held here give, no exchange
            out = moe_ops.routed_moe_share(
                rows, layer["router"]["w"], up, experts["down"],
                cfg.num_experts_per_tok, cfg.expert_offset,
                renormalize=cfg.moe_renormalize, **router, **form,
            )
        else:
            out = moe_ops.routed_moe(
                rows, layer["router"]["w"], up, experts["down"],
                cfg.num_experts_per_tok,
                renormalize=cfg.moe_renormalize, **router, **form,
            )
    if "fc2_latent_proj" in layer:
        with jax.named_scope("omni.moe_latent"):
            out = nn.linear(layer["fc2_latent_proj"], out)
    if "shared_expert" in layer:
        # always-on shared expert: sigmoid-gated per token where the
        # layer has the gate, added whole where it has none (several
        # shared experts are ONE of their summed width: their sum, or at
        # ``shared_expert_scale`` 1 / n their mean)
        se = _dense_mlp(layer["shared_expert"], cfg, x)
        if "shared_gate" in layer:
            gate = jax.nn.sigmoid(
                nn.linear(layer["shared_gate"], x).astype(jnp.float32))
            se = gate.astype(se.dtype) * se
        if cfg.shared_expert_scale != 1.0:  # a mean of shared experts
            se = se * jnp.asarray(cfg.shared_expert_scale, se.dtype)
        out = out + se
    return out.reshape(*lead, out.shape[-1])


def _mlp(layer, cfg: TransformerConfig, x):
    if "experts" in layer:
        return _moe_mlp(layer, cfg, x)
    return _dense_mlp(layer, cfg, x)


@jax.named_scope("omni.proj")
def _rope_tables(cfg: TransformerConfig, positions,
                 kind: Optional[LatentKind] = None):
    """cos/sin tables from positions, 1-D or multimodal 3-D.

    1-D rope: positions [B, S] (prefill) or [B] (decode).  MRoPE
    (cfg.mrope_sections set): [B, 3, S] or [B, 3] — the three streams are
    flattened batch-major to match the activations' [B*S] layout.
    ``kind``: the tables of a kind of layer that has a rotary base of its
    own (a latent kind: and a rotary width; a per-head kind: the model's).
    """
    if kind is not None:
        return compute_rope_freqs(
            positions.reshape(-1),
            kind.qk_rope_head_dim if isinstance(kind, LatentKind)
            else cfg.rotary_dim, kind.rope_theta)
    if cfg.mrope_sections is None:
        return compute_rope_freqs(
            positions.reshape(-1), cfg.rotary_dim, cfg.rope_theta
        )
    if positions.ndim == 3:  # [B, 3, S] -> [3, B*S]
        p = positions.transpose(1, 0, 2).reshape(3, -1)
    else:  # [B, 3] -> [3, B]
        p = positions.T
    return compute_mrope_freqs(
        p, cfg.head_dim, cfg.mrope_sections, cfg.rope_theta
    )


@jax.named_scope("omni.embed")
def _embed_input(params, token_ids, inputs_embeds, embeds_mask):
    """Token embedding, or upstream-stage hidden states as inputs.

    ``inputs_embeds`` replaces the embedding lookup — the embeds-as-input
    path a downstream stage uses to consume upstream hidden states
    (reference: OmniGPUModelRunner._preprocess override,
    worker/gpu_model_runner.py:925).  An optional ``embed_proj`` adapts a
    different upstream width (reference: the talker projects thinker
    hidden states, models/qwen3_omni/qwen3_omni_moe_talker.py).
    ``embeds_mask`` selects per position: True rows take (projected)
    embeds, False rows the token embedding — needed when a preempted
    embeds request re-prefills prompt *and* generated tokens, whose
    embeddings come from the table.
    """
    if inputs_embeds is None:
        return nn.embedding(params["embed"], token_ids)
    x = inputs_embeds
    if "embed_proj" in params:
        proj = params["embed_proj"]
        if "fc1" in proj:
            # two-layer ResizeMLP (the talker's hidden_projection,
            # transformers Qwen3OmniMoeTalkerResizeMLP)
            x = nn.linear(proj["fc2"], jax.nn.silu(nn.linear(proj["fc1"],
                                                             x)))
        else:
            x = nn.linear(proj, x)
    if embeds_mask is not None:
        tok = nn.embedding(params["embed"], token_ids)
        x = jnp.where(embeds_mask[..., None], x, tok)
    return x


def _embed(params, cfg: TransformerConfig, token_ids, inputs_embeds,
           embeds_mask):
    """``_embed_input``, times the model's ``embedding_multiplier`` where
    it has one."""
    x = _embed_input(params, token_ids, inputs_embeds, embeds_mask)
    if cfg.embedding_multiplier == 1.0:
        return x
    with jax.named_scope("omni.embed"):
        return (x.astype(jnp.float32) * cfg.embedding_multiplier).astype(
            x.dtype)


@jax.named_scope("omni.proj")
def _final_norm(params, cfg: TransformerConfig, x):
    return _rms(cfg, x, params["final_norm"]).astype(
        params["final_norm"]["w"].dtype)


def _rope(cfg: TransformerConfig, x, cos, sin):
    if cfg.partial_rotary_factor == 1.0:
        return apply_rope(x, cos, sin)
    return apply_partial_rope(x, cos, sin)


def _join_lanes(parts, width: int):
    """``parts`` side by side on the last axis, zeros after them up to
    ``width``.  A leading part that is whole 128-lane tiles is joined as
    it lies (an aligned ``concatenate``); what follows it is put together
    in blocks as wide as the pieces' common divisor, on the axis before
    the last, then reshaped: a ``concatenate`` of a 64-wide piece and
    another on the last axis is the unaligned join that aborts the XLA
    TPU compiler (``ops/rope.py apply_rope_ref``), and cutting the
    512-wide latent into 64-lane blocks as well cost a relayout of it
    (4% of the cell's device time, PERF.md, PR 32)."""
    if len(parts) > 1 and parts[0].shape[-1] % 128 == 0 \
            and (width - parts[0].shape[-1]) % 128 == 0:
        tail = _join_lanes(parts[1:], width - parts[0].shape[-1])
        return jnp.concatenate([parts[0], tail], axis=-1)
    g = math.gcd(width, *(p.shape[-1] for p in parts))
    lead = parts[0].shape[:-1]
    blocks = [p.reshape(*lead, p.shape[-1] // g, g) for p in parts]
    pad = width - sum(p.shape[-1] for p in parts)
    if pad:
        blocks.append(jnp.zeros((*lead, pad // g, g), parts[0].dtype))
    return jnp.concatenate(blocks, axis=-2).reshape(*lead, width)


def _kind_ropes(cfg: TransformerConfig, positions, mrope_positions=None):
    """{layer kind: (cos, sin)} for the kinds the stack has: one entry for
    every model but one whose sliding-window layers rotate at a base of
    their own; no table at all for a model without positions, and
    ``(None, None)`` for a kind that a model with ``rope_kinds`` leaves
    unrotated."""
    if not cfg.use_rope:
        return {kind: (None, None) for kind in set(cfg.layer_types)}
    first = _rope_tables(cfg, positions if mrope_positions is None
                         else mrope_positions)
    ropes = {kind: first for kind in set(cfg.layer_types)}
    own = cfg.sliding or cfg.sliding_heads
    if own is not None:
        ropes["sliding_attention"] = _rope_tables(cfg, positions, own)
    if cfg.rope_kinds is not None:
        ropes.update({kind: (None, None) for kind in ropes
                      if kind not in cfg.rope_kinds})
    return ropes


def _indexer_inputs(ix, cfg: TransformerConfig, h, c_q, cos, sin):
    """The indexer's operands for normed rows h [T, hidden] and their
    query latent: (q [T, Hi, Di], key [T, Di], w [T, Hi] float32).  The
    first rotary-width dims of a query head and of the key are rotated at
    the token's position; the weights carry both scales."""
    t = h.shape[0]
    n, d, dr = cfg.index_n_heads, cfg.index_head_dim, cfg.qk_rope_head_dim
    q = nn.linear(ix["wq"], c_q).reshape(t, n, d)
    q = _join_lanes([apply_rope_ref(q[..., :dr], cos, sin), q[..., dr:]], d)
    k = nn.linear(ix["wk"], h).astype(jnp.float32)
    k = k - jnp.mean(k, axis=-1, keepdims=True)
    k = (k * jax.lax.rsqrt(jnp.mean(k * k, axis=-1, keepdims=True)
                           + cfg.rms_eps)
         * ix["k_norm"]["w"].astype(jnp.float32)
         + ix["k_norm"]["b"].astype(jnp.float32)).astype(h.dtype)
    k = _join_lanes(
        [apply_rope_ref(k[:, None, :dr], cos, sin), k[:, None, dr:]], d)
    w = nn.linear(ix["w"], h).astype(jnp.float32) * (n ** -0.5 * d ** -0.5)
    return q, k[:, 0], w


def _mla_mixer(layer, cfg: TransformerConfig, lk: LatentKind, h, cos, sin,
               attend):
    """Multi-head latent attention on normed rows h [T, hidden], in its
    absorbed form: ``kv_b`` cut a head into ``W_UK`` [r, nope] and
    ``W_UV`` [r, v], the query is taken into the latent space (``q_lat =
    q_nope W_UK^T``), attention runs over the cached rows themselves —
    ONE kv head whose key is ``[c_kv | k_rope]`` and whose value is the
    key's first ``r`` lanes, every query head on it — and the output
    comes back through ``W_UV``.  The same numbers as expanding every
    cached row to per-head keys and values
    (``q_nope . (c W_UK) = (q_nope W_UK^T) . c``), without a product of
    the context's size.  ``attend(q [T, H, W], row [T, 1, W], None)``
    writes the step's rows and attends (the ``attend`` of the forward,
    its value operand absent) and returns [T, H, r].  Every product with
    ``kv_b`` runs under ``omni.mla_up``.

    ``lk`` holds the widths of the layer's kind (a model may have two).
    What a layer has beyond that it says by its weights: an ``indexer``
    (learned sparse attention: its operands go to ``attend`` as
    ``index=``, which writes the index key beside the row and attends the
    selected keys only), an ``attn_gate`` (one sigmoid gate a head on the
    output); ``cfg.mla_lora_rescale`` multiplies both latents by
    sqrt(hidden / rank) after their norms."""
    m = layer["mla"]
    t = h.shape[0]
    dn, r = lk.qk_nope_head_dim, lk.kv_lora_rank
    width = -(-lk.row // 128) * 128

    def latent(x, norm, rank):
        x = _rms(cfg, x, norm)
        if cfg.mla_lora_rescale:
            x = x.astype(jnp.float32) * (cfg.hidden_size / rank) ** 0.5
        return x.astype(h.dtype)

    with jax.named_scope("omni.proj"):
        c_q = latent(nn.linear(m["q_a"], h), m["q_a_norm"], lk.q_lora_rank)
        q = nn.linear(m["q_b"], c_q).reshape(t, -1, dn + lk.qk_rope_head_dim)
        kv = nn.linear(m["kv_a"], h)
        c_kv = latent(kv[:, :r], m["kv_a_norm"], r)
        # rotate-half over all of the rotary part; the one rotary key is
        # every head's (elementwise work XLA fuses: no kernel at width 64)
        q_rope = apply_rope_ref(q[..., dn:], cos, sin)
        k_rope = apply_rope_ref(kv[:, None, r:], cos, sin)
        row = _join_lanes([c_kv[:, None, :], k_rope], width)
    w_kvb = m["kv_b"]["w"].reshape(r, -1, dn + lk.v_head_dim)
    with jax.named_scope("omni.mla_up"):
        q_lat = jnp.einsum("thn,rhn->thr", q[..., :dn], w_kvb[..., :dn])
        q_full = _join_lanes([q_lat, q_rope], width)
    if "indexer" in layer:
        with jax.named_scope("omni.indexer"):
            index = _indexer_inputs(layer["indexer"], cfg, h, c_q, cos, sin)
        o_lat = attend(q_full, row, None, index=index)
    else:
        o_lat = attend(q_full, row, None)
    o_lat = o_lat.reshape(t, -1, r)
    with jax.named_scope("omni.mla_up"):
        o = jnp.einsum("thr,rhv->thv", o_lat, w_kvb[..., dn:])
    with jax.named_scope("omni.proj"):
        if "attn_gate" in layer:
            gate = jax.nn.sigmoid(
                nn.linear(layer["attn_gate"], h).astype(jnp.float32))
            o = (o.astype(jnp.float32) * gate[..., None]).astype(o.dtype)
        return nn.out_proj(o.reshape(t, -1), layer["o_proj"]["w"],
                           cfg.residual_in_fp32)


def _join_residual(cfg: TransformerConfig, x, branch):
    """``x + residual_multiplier * branch``: the product and the sum in
    float32 and one rounding to the stream's type; the plain sum where the
    model has no such multiplier."""
    if cfg.residual_multiplier == 1.0:
        return x + branch
    return (x.astype(jnp.float32) + cfg.residual_multiplier
            * branch.astype(jnp.float32)).astype(x.dtype)


def _layer_step(layer, cfg: TransformerConfig, x, cos, sin, attend,
                kind: str = "full_attention"):
    """One transformer block: norm → mixer → residual → norm → MLP →
    residual; or, in a stack written one sublayer a layer (Nemotron-H),
    the ONE of those two halves that the layer's weights hold (an
    ``input_norm`` with its mixer, or a ``post_norm`` with its MLP); or,
    under ``cfg.parallel_block`` (a mixer AND an MLP under the layer's one
    ``input_norm``: the Cohere family), ``x + mixer(n(x)) +
    mlp(n(x))``, the two branches joined in one sum.  In a
    softmax-attention layer the mixer is qkv+rope →
    ``attend(q, k, v)``, which supplies the attention variant (dense
    causal / cached-context chunked / paged decode) and returns o with
    leading dims matching ``x``'s; in a latent-attention layer (``"mla"``
    in the layer's weights) the same ``attend`` gets the absorbed query,
    the latent row as the key and no value (``_mla_mixer``); in a layer
    that carries a state (``kind`` is one of ``state_layers.KINDS``)
    ``attend`` is the ``recur`` of that kind's mixer.  The MLP is the sparse
    one where the layer holds ``experts``, a dense SwiGLU where it does not.
    One body serves every forward so the variants cannot silently diverge."""
    if cfg.residual_in_fp32:
        x = x.astype(jnp.float32)
    if "input_norm" in layer:
        with jax.named_scope("omni.proj"):
            h = _rms(cfg, x, layer["input_norm"]).astype(
                layer["input_norm"]["w"].dtype)
        o = _mixer(layer, cfg, h, cos, sin, attend, kind)
        if cfg.parallel_block:
            # one norm a layer: the MLP reads ``h`` too
            return _join_branches(cfg, x, o, _mlp_branch(layer, cfg, h))
        with jax.named_scope("omni.proj"):
            x = _join_residual(cfg, x, o)
    if "post_norm" not in layer:
        return x
    with jax.named_scope("omni.proj"):
        h = _rms(cfg, x, layer["post_norm"]).astype(
            layer["post_norm"]["w"].dtype)
    y = _mlp_branch(layer, cfg, h)
    with jax.named_scope("omni.moe" if "experts" in layer else "omni.mlp"):
        return _join_residual(cfg, x, y)


def _mlp_branch(layer, cfg: TransformerConfig, h):
    """The feed-forward part's branch of normed rows ``h``, whole under
    tensor parallelism."""
    with jax.named_scope("omni.moe" if "experts" in layer else "omni.mlp"):
        y = _mlp(layer, cfg, h)
        if cfg.tp_axis is not None:
            y = jax.lax.psum(y, cfg.tp_axis)
        return y


@jax.named_scope("omni.proj")
def _join_branches(cfg: TransformerConfig, x, o, y):
    """``x + residual_multiplier * (o + y)`` of a parallel block: the sum
    in float32 and ONE rounding to the stream's type, where two sums in
    the stream's type would round twice."""
    both = o.astype(jnp.float32) + y.astype(jnp.float32)
    if cfg.residual_multiplier != 1.0:
        both = cfg.residual_multiplier * both
    return (x.astype(jnp.float32) + both).astype(x.dtype)


def _mixer(layer, cfg: TransformerConfig, h, cos, sin, attend, kind: str):
    """``mixer(h)`` for normed rows ``h``: the branch that the first half
    of ``_layer_step`` adds to the stream, whole under tensor parallelism."""
    b = h.shape[:-1]
    record = state_layers.KINDS.get(kind)
    if record:
        o = record.mixer(layer[record.key], cfg, h.reshape(-1, h.shape[-1]),
                         attend).reshape(*b, -1)
    elif "mla" in layer:
        o = _mla_mixer(layer, cfg, cfg.latent_kind(kind),
                       h.reshape(-1, h.shape[-1]), cos, sin,
                       attend).reshape(*b, -1)
    else:
        with jax.named_scope("omni.proj"):
            q, k, v, gate = _qkv(layer, cfg, h.reshape(-1, h.shape[-1]))
            if cos is not None:  # a kind, or a model, without positions
                q = _rope(cfg, q, cos, sin)
                k = _rope(cfg, k, cos, sin)
        # a layer whose softmax has a learned sink says so by its weights
        o = attend(q, k, v, **({"sinks": layer["sinks"]}
                               if "sinks" in layer else {})).reshape(*b, -1)
        if gate is not None:
            # the output gate is part of gated attention: billed to it
            with jax.named_scope("omni.attn"):
                o = (o.astype(jnp.float32) * jax.nn.sigmoid(
                    gate.reshape(*b, -1).astype(jnp.float32))).astype(o.dtype)
        with jax.named_scope("omni.proj"):
            o = nn.out_proj(o, layer["o_proj"]["w"], cfg.residual_in_fp32)
    if cfg.tp_axis is not None:
        with jax.named_scope("omni.proj"):
            # row-parallel o_proj: each shard holds a partial sum
            o = jax.lax.psum(o, cfg.tp_axis)
    return o


class HybridCaches(NamedTuple):
    """The cache operand of a model with layers that carry a state, where
    other models pass their list of pages: two kinds of cache in one
    pytree, so that a step donates and rebinds both together
    (docs/kv_cache.md)."""

    kv: list     # per softmax-attention layer: (k, v) pages
    # per layer that carries a state, its slots, a tuple of arrays
    # ``[slots, ...]``, one a shape of ``TransformerConfig.state_shapes``
    state: list


def _layer_caches(params, cfg: TransformerConfig, kv_caches, state_caches):
    """(layer, its kind, its cache) down the stack: the (k, v) pages of a
    softmax-attention layer ((latent pool, None) of a latent-attention
    layer: the write and the kernels take the absent value as "read it
    from the key's row"), the state slots of a layer that carries one.
    ``kv_caches`` holds one entry for each attention layer only,
    ``state_caches`` one for each layer that carries a state; a layer that
    is its feed-forward part alone has no mixer and so no cache (None)."""
    kv, st = iter(kv_caches), iter(state_caches or ())
    for layer, kind in zip(params["layers"], cfg.layer_types):
        yield layer, kind, (None if kind == FEED_FORWARD else next(
            st if kind in state_layers.KINDS else kv))


def _read_state(cache, slots, fresh, scope):
    """The rows' carried state out of their slots ([rows, ...] an array of
    the layer's state); zeros for a row that starts its sequence
    (``fresh``), which is how a slot is zeroed when it is handed out.
    Padding rows carry a slot past the end: they read a clipped row here
    and their write is dropped."""
    with jax.named_scope(scope):
        rows = [a.at[slots].get(mode="clip") for a in cache]
        return tuple(
            jnp.where(fresh[(slice(None),) + (None,) * (a.ndim - 1)], 0, a)
            for a in rows)


def _write_state(cache, slots, state, scope):
    with jax.named_scope(scope):
        return tuple(a.at[slots].set(new.astype(a.dtype), mode="drop")
                     for a, new in zip(cache, state))


def _stateful_recur(record: state_layers.StateKind, rule, cache, slots,
                    fresh, new_states):
    """The ``recur`` of ``record``'s mixer for a forward that carries
    state: the rows' slots are read (under the record's scope),
    ``rule(*the mixer's operands, *the state's arrays)`` runs the ragged
    or the one-token form and returns the output and the new state, and the
    slots are written back into ``new_states``.  The last
    ``record.in_place`` arrays of the layer's state are not read out: the
    rule gets the pools themselves with ``slots`` and ``fresh`` after them,
    and returns them updated."""
    scope = record.scope
    moved = cache[:len(cache) - record.in_place]
    pools = cache[len(moved):]

    def recur(*operands):
        o, *state = rule(*operands, *_read_state(moved, slots, fresh, scope),
                         *((*pools, slots, fresh) if pools else ()))
        new_states.append(
            _write_state(moved, slots, state[:len(moved)], scope)
            + tuple(state[len(moved):]))
        return o

    return recur


def forward_hidden(
    params,
    cfg: TransformerConfig,
    token_ids: jax.Array,  # [B, S]
    positions: Optional[jax.Array] = None,  # [B, S]
    inputs_embeds: Optional[jax.Array] = None,  # [B, S, hidden]
    attn_mask: Optional[jax.Array] = None,  # [B, S] 1=attendable key
    drop_last_layers: int = 0,
    apply_final_norm: bool = True,
    collect_hidden_layers: tuple = (),
    embeds_mask: Optional[jax.Array] = None,  # [B, S] True=row uses embeds
) -> jax.Array:
    """Full-sequence causal forward returning final hidden states
    [B, S, hidden] (the text-encoder path; also prefill without cache).

    ``attn_mask`` excludes padded KEY positions on top of causality —
    needed when padding sits mid-sequence (LongCat-Image pads the user
    prompt to a fixed length BETWEEN the template prefix and suffix, so
    suffix tokens would otherwise attend pad keys).

    ``drop_last_layers=1, apply_final_norm=False`` yields the HF
    ``output_hidden_states[-2]`` convention (the penultimate layer's
    raw output) that Z-Image conditions on (pipeline_z_image.py:261-266).

    ``collect_hidden_layers``: HF hidden_states indices (0 = embeddings,
    k = after layer k) to gather and concatenate on the feature axis —
    the Flux2-Klein text conditioning stacks Qwen3 layers (9, 18, 27)
    (pipeline_flux2_klein.py:247-302).  When set, the concatenation is
    returned instead of the final hidden states.
    """
    if cfg.num_window_layers or cfg.index_topk:
        raise NotImplementedError(
            "the cache-less full-sequence forward has no window and no "
            "learned selection: a model with sliding-window or sparse "
            "layers is served through forward_unified / forward_decode")
    b, s = token_ids.shape
    x = _embed(params, cfg, token_ids, inputs_embeds, embeds_mask)
    if positions is None:
        shape = (b, s) if cfg.mrope_sections is None else (b, 3, s)
        positions = jnp.broadcast_to(jnp.arange(s)[None, :], shape)
    ropes = _kind_ropes(cfg, positions)

    def attend(q, k, v):
        with jax.named_scope("omni.attn"):
            if v is None:
                # a latent layer, absorbed: one kv head whose row is key
                # and value; the latent part of the output is kept
                o = flash_attention(
                    q.reshape(b, s, -1, q.shape[-1]),
                    k.reshape(b, s, 1, k.shape[-1]),
                    k.reshape(b, s, 1, k.shape[-1]),
                    causal=True, kv_mask=attn_mask,
                    scale=cfg.attn_kwargs["scale"])
                return o[..., :cfg.kv_lora_rank]
            if cfg.value_dim != cfg.head_dim:
                raise NotImplementedError(
                    "the cache-less forward takes a value as wide as its "
                    "key")
            return flash_attention(
                q.reshape(b, s, -1, cfg.head_dim),
                k.reshape(b, s, -1, cfg.head_dim),
                v.reshape(b, s, -1, cfg.head_dim),
                causal=True,
                kv_mask=attn_mask,
                **cfg.attn_kwargs,
            )

    def recur_of(record: state_layers.StateKind):
        def recur(*operands):
            # no cache: every sequence starts from a zero state and the
            # final state is dropped; the [B, S] batch is B packed segments
            # of S rows, and a kind whose rule updates a pool in place gets
            # a pool of the batch's own, every slot fresh
            state = [jnp.zeros((b, *shape), jnp.float32)
                     for shape in record.shapes(cfg)]
            if record.in_place:
                state += [jnp.arange(b, dtype=jnp.int32),
                          jnp.ones((b,), bool)]
            rule = record.ragged(cfg, jnp.arange(b + 1, dtype=jnp.int32) * s,
                                 jnp.full((b,), s, jnp.int32))
            return rule(*operands, *state)[0]

        return recur

    layers = params["layers"]
    if drop_last_layers:
        layers = layers[:len(layers) - drop_last_layers]
    collected = {0: x} if 0 in collect_hidden_layers else {}
    for li, (layer, kind) in enumerate(zip(layers, cfg.layer_types)):
        record = state_layers.KINDS.get(kind)
        x = _layer_step(layer, cfg, x, *ropes[kind],
                        recur_of(record) if record else attend, kind)
        if li + 1 in collect_hidden_layers:
            collected[li + 1] = x
    if collect_hidden_layers:
        missing = [k for k in collect_hidden_layers if k not in collected]
        if missing:
            raise ValueError(
                f"collect_hidden_layers {missing} out of range for "
                f"{len(layers)} layers")
        return jnp.concatenate(
            [collected[k] for k in collect_hidden_layers], axis=-1)
    if not apply_final_norm:
        return x
    return _final_norm(params, cfg, x)


@jax.named_scope("omni.lm_head")
def logits_from_hidden(params, cfg: TransformerConfig, hidden: jax.Array):
    if cfg.tie_word_embeddings:
        # embed table is replicated under TP — logits already full
        logits = hidden @ params["embed"]["w"].T
        if cfg.logits_scaling != 1.0:
            logits = logits / cfg.logits_scaling
        return logits
    if cfg.logits_scaling != 1.0:
        raise NotImplementedError("logits_scaling is built for a head "
                                  "that is the embedding transposed")
    logits = nn.linear(params["lm_head"], hidden)
    if cfg.tp_axis is not None:
        # column-parallel lm_head: reassemble the vocab axis
        logits = jax.lax.all_gather(
            logits, cfg.tp_axis, axis=logits.ndim - 1, tiled=True)
    return logits


def _paged_attend(cfg: TransformerConfig, kind: str, cache, new_caches,
                  slots, tables, attention, scores):
    """The ``attend`` of a forward over paged caches for one layer of
    ``kind``: write the step's rows at ``slots`` into the layer's pools,
    then ``attention(q, k_cache, v_cache, tables, **kwargs)``.  A layer's
    cache is ``(k, v)``, ``(pool, None)`` for a latent layer, and ``(pool,
    None, index_pool)`` for one with an indexer: its index key is written
    at the same slots, and where the tables reach past ``index_topk`` keys
    ``scores(q, w, index_pool, tables)`` and the selection by rank give the
    attention its ``keep`` (a context that short keeps every key, by the
    shapes of the call).  A layer with a learned sink hands its ``sinks``
    through to the attention."""
    sliding = kind == "sliding_attention"
    kwargs = cfg.attn_kwargs_of(kind)

    def attend(q, k, v, index=None, **sinks):
        k_cache, v_cache = cache[:2]
        with jax.named_scope("omni.kv_write"):
            k_cache, v_cache = write_kv_cache(k_cache, v_cache, k, v, slots)
            if index is not None:
                index_pool, _ = write_kv_cache(
                    cache[2], None, index[1][:, None, :], None, slots)
        keep = {}
        if index is None:
            new_caches.append((k_cache, v_cache))
        else:
            new_caches.append((k_cache, v_cache, index_pool))
            if tables.shape[1] * k_cache.shape[2] > cfg.index_topk:
                with jax.named_scope("omni.indexer"):
                    keep["keep"] = indexer_ops.topk_keep(
                        scores(index[0], index[2], index_pool, tables),
                        cfg.index_topk)
        with jax.named_scope(
                "omni.attn_window" if sliding else "omni.attn"):
            return attention(q, k_cache, v_cache, tables, **kwargs, **keep,
                             **sinks)

    return attend


def forward_unified(
    params,
    cfg: TransformerConfig,
    token_ids: jax.Array,    # [T] token-packed mixed batch
    positions: jax.Array,    # [T] global positions ([3, T] under mrope)
    kv_caches: list,
    slot_mapping: jax.Array,  # [T] flat slots (-1 for padding rows)
    page_tables: jax.Array,   # [S, max_pages]
    seq_lens: jax.Array,      # [S] context incl. this step's tokens
    cu_q_lens: jax.Array,     # [S+1] aligned packed segment starts
    q_lens: jax.Array,        # [S] real token count per sequence
    num_seqs: jax.Array,      # [1]
    inputs_embeds: Optional[jax.Array] = None,  # [T, embed_width]
    embeds_mask: Optional[jax.Array] = None,    # [T] True=row uses embeds
    deepstack: Optional[jax.Array] = None,      # [n_deep, T, hidden]
    state_slots: Optional[jax.Array] = None,    # [S] slot of each sequence
    window_pages: Optional[tuple] = None,       # (slots [T], tables [S, n])
):
    """Unified ragged mixed-batch forward: prefill chunks and 1-token
    decode rows share ONE token-packed execution (ops/
    ragged_paged_attention.py; layout contract in its module docstring
    and docs/ragged_batching.md).  Each layer scatters the step's KV
    through the slot mapping, then attends the paged context raggedly —
    replacing the fresh/chunk/decode triple dispatch for mixed steps.

    ``inputs_embeds``/``embeds_mask`` are the embeds-as-input path
    scattered onto the packed token axis (see ``_embed_input``);
    ``deepstack`` carries multiscale visual features per packed row
    (zeros at non-visual rows), level ``i`` added to the residual
    stream after decoder layer ``i`` (reference: Qwen3-Omni thinker
    deepstack injection, qwen3_omni_moe_thinker.py:177-178), so
    embeds/deepstack batches ride the unified dispatch instead of a
    separately padded executable.

    A model with layers that carry a state passes ``HybridCaches`` as
    ``kv_caches`` (beside the pages, for each Gated DeltaNet layer the
    convolution's and the rule's state, for each gated short convolution
    its last inputs, for each Mamba-2 layer the convolution's inputs and
    the rule's state, one slot a sequence) and ``state_slots``: a
    sequence's rows read its slot at the chunk's start (zeros where the
    chunk starts the sequence) and write it at the chunk's end, through
    the ragged form of the layer's rule (a Mamba-2 layer's state is
    updated in its slot: ``state_layers.StateKind.in_place``).

    A model with sliding-window layers passes ``window_pages``: the slot
    mapping and the page tables of the window kind of page, which its
    sliding layers write and read in place of ``slot_mapping`` and
    ``page_tables`` (docs/kv_cache.md, "Two kinds of page").

    Returns (hidden [T, hidden], new kv_caches of the type passed).
    """
    hybrid = isinstance(kv_caches, HybridCaches)
    kv_caches, state_caches = kv_caches if hybrid else (kv_caches, None)
    x = _embed(params, cfg, token_ids, inputs_embeds, embeds_mask)
    # [3, T] -> the [B, 3, S] convention with B=1
    ropes = _kind_ropes(cfg, positions, None if cfg.mrope_sections is None
                        else positions[None])
    new_caches, new_states = [], []

    def attention(q, k_cache, v_cache, tables, **kwargs):
        return ragged_paged_attention(
            q, k_cache, v_cache, tables, cu_q_lens, q_lens, seq_lens,
            num_seqs, **kwargs)

    def scores(q, w, pool, tables):
        return indexer_ops.indexer_scores(
            q, w, pool, tables, cu_q_lens, q_lens, seq_lens, num_seqs)

    for i, (layer, kind, cache) in enumerate(
            _layer_caches(params, cfg, kv_caches, state_caches)):
        attend = _paged_attend(
            cfg, kind, cache, new_caches,
            *(window_pages if kind == "sliding_attention"
              else (slot_mapping, page_tables)), attention, scores)

        # a sequence starts where its context is this step's tokens alone
        # (a layer at a time, as the jaxpr has had it: the steps' digests
        # in tests/ops/test_ragged_tpu_compile.py hold the order)
        fresh = seq_lens == q_lens
        record = state_layers.KINDS.get(kind)
        if record:
            attend = _stateful_recur(
                record, record.ragged(cfg, cu_q_lens, q_lens), cache,
                state_slots, fresh, new_states)
        x = _layer_step(layer, cfg, x, *ropes[kind], attend, kind)
        if deepstack is not None and i < deepstack.shape[0]:
            with jax.named_scope("omni.embed"):
                x = x + deepstack[i].astype(x.dtype)
    if hybrid:
        new_caches = HybridCaches(new_caches, new_states)
    return _final_norm(params, cfg, x), new_caches


def forward_decode(
    params,
    cfg: TransformerConfig,
    token_ids: jax.Array,  # [B]
    positions: jax.Array,  # [B]
    kv_caches: list,
    slot_mapping: jax.Array,  # [B]
    block_tables: jax.Array,  # [B, max_pages]
    context_lens: jax.Array,  # [B] (including the new token)
    state_slots: Optional[jax.Array] = None,  # [B] slot of each row
    window_pages: Optional[tuple] = None,     # (slots [B], tables [B, n])
):
    """One decode step over a batch of sequences with paged attention;
    the Gated DeltaNet and short-convolution layers of a hybrid model
    update each row's state slot through the one-token form of their rule
    (``HybridCaches`` and ``state_slots`` as in ``forward_unified``).

    Returns (hidden [B, hidden], new kv_caches of the type passed).
    """
    hybrid = isinstance(kv_caches, HybridCaches)
    kv_caches, state_caches = kv_caches if hybrid else (kv_caches, None)
    x = _embed(params, cfg, token_ids, None, None)  # [B, hidden]
    ropes = _kind_ropes(cfg, positions)
    new_caches, new_states = [], []

    def attention(q, k_cache, v_cache, tables, **kwargs):
        return paged_attention(q, k_cache, v_cache, tables, context_lens,
                               **kwargs)

    def scores(q, w, pool, tables):
        return indexer_ops.indexer_scores_decode(q, w, pool, tables,
                                                 context_lens)

    for layer, kind, cache in _layer_caches(params, cfg, kv_caches,
                                            state_caches):
        attend = _paged_attend(
            cfg, kind, cache, new_caches,
            *(window_pages if kind == "sliding_attention"
              else (slot_mapping, block_tables)), attention, scores)

        fresh = context_lens <= 1
        record = state_layers.KINDS.get(kind)
        if record:
            attend = _stateful_recur(record, record.decode(cfg), cache,
                                     state_slots, fresh, new_states)
        x = _layer_step(layer, cfg, x, *ropes[kind], attend, kind)
    if hybrid:
        new_caches = HybridCaches(new_caches, new_states)
    return _final_norm(params, cfg, x), new_caches
