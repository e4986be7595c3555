"""The ragged paged-attention kernel compiles for a v5e at real widths —
ahead of time, against a DESCRIBED chip (no chip attached, nothing
runs): what Mosaic refuses (a slice off the tiling, a relayout it
cannot do, too much VMEM) fails here at no chip time.  Numerics and
every time come from the chip (scripts/ragged_kernel_sweep.py,
chip_smoke.py); a compile that passes is not a chip run.

The topology is described inside a fixture, never at import: only one
process may hold the TPU's library, and every xdist worker imports this
file (the `on-chip-measurement` guide, section 2).  Keep TPU compiles in
this one file."""

import importlib

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

rpa = importlib.import_module("vllm_omni_tpu.ops.ragged_paged_attention")
from vllm_omni_tpu.ops.autotune import auto_ragged_blocks  # noqa: E402


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here: nothing to check
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


# the benchmark's thinker (32 q / 4 kv heads), its TP=4 shard (one kv
# head), the int8 cache, and the other group sizes of the package's
# autoregressive models (talker / TTS LMs: group 4 and no GQA at all)
SHAPES = {
    "thinker_bf16": dict(t=4096, hkv=4, group=8, quantized=False),
    "thinker_int8": dict(t=4096, hkv=4, group=8, quantized=True),
    "tp4_shard_bf16": dict(t=1024, hkv=1, group=8, quantized=False),
    "group4_bf16": dict(t=1024, hkv=4, group=4, quantized=False),
    "group1_bf16": dict(t=1024, hkv=4, group=1, quantized=False),
    "group1_int8": dict(t=1024, hkv=4, group=1, quantized=True),
}


@pytest.mark.parametrize("name", sorted(SHAPES))
def test_ragged_kernel_compiles_for_v5e(name, one_chip, monkeypatch):
    shape = SHAPES[name]
    t, hkv, group, quantized = (shape[k] for k in
                                ("t", "hkv", "group", "quantized"))
    d, page, pool, s_max, max_pages = 128, 16, 8192, 64, 528
    monkeypatch.setattr(rpa, "interpret_flag", lambda: False)

    def sds(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    data = sds((hkv, pool, page, d), jnp.int8 if quantized else jnp.bfloat16)
    cache = (data, sds((hkv, pool), jnp.float32)) if quantized else data
    q_block, ppb, slots = auto_ragged_blocks(
        head_dim=d, page_size=page, group=group,
        kv_itemsize=1 if quantized else 2, q_itemsize=2,
        quantized=quantized)

    def step(q, k, v, tables, cu, q_lens, seq_lens, num_seqs):
        return rpa._ragged_attention(
            q, k, v, tables, cu, q_lens, seq_lens, num_seqs, None,
            rpa.DEFAULT_TOKEN_BLOCK, True, q_block, ppb, slots)

    compiled = jax.jit(step).lower(
        sds((t, hkv * group, d), jnp.bfloat16), cache, cache,
        sds((s_max, max_pages), jnp.int32), sds((s_max + 1,), jnp.int32),
        sds((s_max,), jnp.int32), sds((s_max,), jnp.int32),
        sds((1,), jnp.int32)).compile()
    assert "tpu_custom_call" in compiled.as_text()
