"""Gated-MLP activations (reference: vLLM's fused SiLU-mul CUDA op,
SURVEY.md §2.10).  On TPU, XLA fuses these elementwise ops into the
surrounding matmuls, so the idiomatic implementation is plain jnp — kept
here as named ops so model code reads like the reference's layer inventory.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def silu_mul(gate_up: jax.Array) -> jax.Array:
    """Input [..., 2*d] = concat(gate, up); returns silu(gate) * up."""
    gate, up = jnp.split(gate_up, 2, axis=-1)
    return jax.nn.silu(gate) * up


def gelu_tanh_mul(gate_up: jax.Array) -> jax.Array:
    gate, up = jnp.split(gate_up, 2, axis=-1)
    return jax.nn.gelu(gate, approximate=True) * up


def relu2(x: jax.Array) -> jax.Array:
    """``relu(x)^2``: the activation of an UNGATED MLP (Nemotron-H's
    ``mlp_hidden_act`` "relu2"): ``down(relu2(up(x)))``, no gate half."""
    return jnp.square(jax.nn.relu(x))


#: the activation of an ungated MLP, by the name a configuration gives it
UNGATED = {"relu2": relu2, "silu": jax.nn.silu}
