"""A record of ``models/common/state_layers.KINDS`` holds everything the
shared decoder's forwards read of a kind of layer that carries a state: a
further kind that forgets a field (its one-token rule, say) fails here, on
the CPU, and not in a step on the chip."""

import dataclasses
import functools
import importlib

import jax
import jax.numpy as jnp
import pytest

from vllm_omni_tpu.models.common import state_layers
from vllm_omni_tpu.models.common import transformer as tfm

#: kind -> the model whose tiny configuration has layers of it
MODEL_OF = {"linear_attention": "qwen3_next", "conv": "lfm2_moe",
            "mamba": "granite_moe_hybrid"}
SLOTS, ROWS = 4, 32


def test_the_table_is_the_configs_state_kinds():
    assert tfm.STATE_KINDS == tuple(state_layers.KINDS)
    assert set(MODEL_OF) == set(state_layers.KINDS)
    keys = [record.key for record in state_layers.KINDS.values()]
    assert len(set(keys)) == len(keys)


@pytest.mark.parametrize("kind", sorted(state_layers.KINDS))
def test_a_record_has_what_every_forward_reads(kind):
    record = state_layers.KINDS[kind]
    assert record.scope.startswith("omni.") and record.key
    tiny = importlib.import_module(
        f"vllm_omni_tpu.models.{MODEL_OF[kind]}").tiny_config()
    # one layer of the kind under one attention layer
    cfg = dataclasses.replace(tiny, num_layers=2,
                              layer_kinds=(kind, "full_attention"))
    shapes = record.shapes(cfg)
    assert cfg.state_shapes(kind) == shapes
    assert 0 <= record.in_place <= len(shapes)

    i32, f32 = jnp.int32, jnp.float32

    def sds(dims, dtype=i32):
        return jax.ShapeDtypeStruct(dims, dtype)

    params = jax.eval_shape(functools.partial(
        tfm.init_params, cfg=cfg, dtype=f32), jax.random.PRNGKey(0))
    assert record.key in params["layers"][0]
    assert not any(other.key in params["layers"][1]
                   for other in state_layers.KINDS.values())
    lay = cfg.kv_layout
    pool = sds((lay.heads, 8, 16, lay.width), f32)
    caches = tfm.HybridCaches(
        [(pool, pool)], [tuple(sds((SLOTS, *shape), f32)
                               for shape in shapes)])

    hidden, new = jax.eval_shape(
        lambda p, kv, tok, pos, slots, tables, ctx, st: tfm.forward_decode(
            p, cfg, tok, pos, kv, slots, tables, ctx, state_slots=st),
        params, caches, sds((SLOTS,)), sds((SLOTS,)), sds((SLOTS,)),
        sds((SLOTS, 4)), sds((SLOTS,)), sds((SLOTS,)))
    assert hidden.shape == (SLOTS, cfg.hidden_size)
    assert jax.tree.map(lambda a: (a.shape, a.dtype), new.state) \
        == jax.tree.map(lambda a: (a.shape, a.dtype), caches.state)

    hidden, new = jax.eval_shape(
        lambda p, kv, tok, pos, slots, tables, lens, cu, q_lens, n, st:
        tfm.forward_unified(p, cfg, tok, pos, kv, slots, tables, lens, cu,
                            q_lens, n, state_slots=st),
        params, caches, sds((ROWS,)), sds((ROWS,)), sds((ROWS,)),
        sds((SLOTS, 4)), sds((SLOTS,)), sds((SLOTS + 1,)), sds((SLOTS,)),
        sds((1,)), sds((SLOTS,)))
    assert hidden.shape == (ROWS, cfg.hidden_size)
    assert jax.tree.map(lambda a: (a.shape, a.dtype), new.state) \
        == jax.tree.map(lambda a: (a.shape, a.dtype), caches.state)

    hidden = jax.eval_shape(lambda p, tok: tfm.forward_hidden(p, cfg, tok),
                            params, sds((2, 16)))
    assert hidden.shape == (2, 16, cfg.hidden_size)
