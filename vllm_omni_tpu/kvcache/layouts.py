"""What each cache layout cannot carry.

A model's cache is pages of per-head (K, V) rows unless its configuration
says otherwise, and five mechanisms read, move, shard or roll back a page's
contents by that layout.  Four layouts are served beside it
(docs/kv_cache.md): a state slot a sequence beside the pages (recurrent
layers), pages of a second kind that are given back once they slide out of
reach (sliding-window layers), one shared pool of latent rows a layer
(multi-head latent attention), and per-head pools of two shapes (a key
wider than its value, or kv heads that differ by kind of layer).  None of
the five carries any of the four yet, each for a reason of its own: this
table is the one place that says which and why.  ``LLMEngine`` refuses what a configuration asks for before
anything is built (``refusal``); ``ARModelRunner``, which can be built
without an engine, refuses at its own entry points from the same cells
(``cannot_carry``).  Prefix hits and preemption are not here: the cache
manager handles them a sequence at a time.
"""

from __future__ import annotations

from typing import Callable, Iterable, NamedTuple, Optional

INT8 = "kv_cache_dtype int8"
TRANSFER = "kv_transfer"
OFFLOAD = "kv_offload"
SPECULATIVE = "speculative decoding"
TENSOR_PARALLEL = "tensor_parallel_size > 1"
MECHANISMS = (INT8, TRANSFER, OFFLOAD, SPECULATIVE, TENSOR_PARALLEL)


class Layout(NamedTuple):
    has: Callable[[object], bool]  # whether a model's configuration has it
    keeps: str                     # what such a model keeps
    reasons: dict[str, str]        # mechanism -> why it cannot ride it


def _latent(model_cfg) -> bool:
    layout = getattr(model_cfg, "kv_layout", None)
    return layout is not None and layout.kind == "latent"


def _two_shapes(model_cfg) -> bool:
    if _latent(model_cfg) or not hasattr(model_cfg, "value_dim"):
        return False
    return (model_cfg.value_dim != model_cfg.head_dim
            or model_cfg.sliding_heads is not None)


LAYOUTS = {
    "recurrent state": Layout(
        lambda model_cfg: getattr(model_cfg, "num_state_layers", 0) > 0,
        "a model with recurrent (linear-attention, short-convolution or "
        "state-space) layers keeps per-sequence state beside its KV pages",
        {INT8: "the state beside the pages is float32 and has no quantized "
               "layout",
         TRANSFER: "inject_kv / extract_kv and a non-colocated engine_role "
                   "move pages, and part of a sequence lies in its state "
                   "slot",
         OFFLOAD: "parking / tiered offload of pages leaves the state slot "
                  "behind",
         SPECULATIVE: "a rejected draft cannot roll a recurrent state back",
         TENSOR_PARALLEL: "no sharded layout for the state cache"}),
    "window pages": Layout(
        lambda model_cfg: getattr(model_cfg, "num_window_layers", 0) > 0,
        "a model with sliding-window layers keeps two kinds of page",
        {INT8: "no quantized layout for the window kind's rows",
         TRANSFER: "the inject_kv / extract_kv payload of a transfer or a "
                   "non-colocated engine_role carries one kind of page",
         OFFLOAD: "a parked run would lack the window pages that were "
                  "given back",
         SPECULATIVE: "a rejected draft rolls back below pages already "
                      "given back",
         TENSOR_PARALLEL: "no sharded layout for the window kind's pool"}),
    "latent row": Layout(
        _latent,
        "a model with multi-head latent attention keeps one latent pool a "
        "layer",
        {INT8: "the latent cache has no quantized layout (one absmax scale "
               "a page would span the latent and the rotary key alike)",
         TRANSFER: "the inject_kv / extract_kv payload of a transfer or a "
                   "non-colocated engine_role is (K, V) per head, which "
                   "does not describe a latent cache's one shared pool a "
                   "layer",
         OFFLOAD: "parking / tiered offload extract the same (K, V) payload",
         SPECULATIVE: "the verify rows' path over a latent cache is not "
                      "built",
         TENSOR_PARALLEL: "the latent cache is one kv head that every query "
                          "head reads, and has no sharded layout yet"}),
    "pools of two shapes": Layout(
        _two_shapes,
        "a model whose keys are wider than its values, or whose kinds of "
        "layer differ in their kv heads, keeps K and V pools of more than "
        "one shape",
        {INT8: "the quantized layout has one row width for K and V, and "
               "one absmax scale a page would span a key's padding lanes",
         TRANSFER: "the inject_kv / extract_kv payload of a transfer or a "
                   "non-colocated engine_role is [Hkv, seq, D] halves of "
                   "ONE head count and ONE width for every layer",
         OFFLOAD: "parking / tiered offload extract the same one-shape "
                  "payload",
         SPECULATIVE: "the verify rows' path over K and V pools of "
                      "different widths and head counts is not built",
         TENSOR_PARALLEL: "two kinds of layer divide their kv heads by "
                          "different counts, and no sharded layout holds a "
                          "V pool narrower than its K pool"}),
}


def layouts_of(model_cfg) -> tuple[str, ...]:
    """The layouts of ``LAYOUTS`` this model's cache has; () for per-head
    (K, V) pages alone, and for a configuration that describes no cache."""
    return tuple(name for name, layout in LAYOUTS.items()
                 if layout.has(model_cfg))


def cannot_carry(model_cfg) -> dict[str, Optional[str]]:
    """For each of ``MECHANISMS``: None, or why this model's layouts cannot
    carry it (the cells of its layouts, joined)."""
    cells = [LAYOUTS[name].reasons for name in layouts_of(model_cfg)]
    return {m: "; ".join(cell[m] for cell in cells) or None
            for m in MECHANISMS}


def refusal(model_cfg, asked: Iterable[str]) -> Optional[str]:
    """One message naming the model's layouts and every mechanism of
    ``asked`` that they cannot carry; None where all of them ride."""
    reasons = cannot_carry(model_cfg)
    refused = [f"{m} ({reasons[m]})" for m in asked if reasons[m]]
    if not refused:
        return None
    keeps = "; ".join(LAYOUTS[name].keeps for name in layouts_of(model_cfg))
    return (f"{keeps}, which these mechanisms do not carry yet: "
            + "; ".join(refused))
