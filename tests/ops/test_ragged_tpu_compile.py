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
import numpy as np
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
    # Qwen3-Next's gated-attention layers: head width 256, 2 kv heads,
    # contexts to 33280 (the second shape the kernel is measured at)
    "head256_bf16": dict(t=4096, hkv=2, group=8, quantized=False, d=256,
                         pool=32768, s_max=32, max_pages=2080),
    # GLM-4.7-Flash's latent cache: one shared pool whose 640-lane row
    # ([c_kv 512 | k_rope 64 | 64 zeros]) is the key and, in its first
    # 512 lanes, the value; 20 query heads on the one kv head
    "latent_bf16": dict(t=4096, hkv=1, group=20, quantized=False, d=640,
                        v_dim=512, pool=16384, s_max=32, max_pages=1104),
}


@pytest.mark.parametrize("name", sorted(SHAPES))
def test_ragged_kernel_compiles_for_v5e(name, one_chip, monkeypatch):
    shape = SHAPES[name]
    t, hkv, group, quantized = (shape[k] for k in
                                ("t", "hkv", "group", "quantized"))
    d, page = shape.get("d", 128), 16
    pool, s_max, max_pages = (shape.get(k, v) for k, v in (
        ("pool", 8192), ("s_max", 64), ("max_pages", 528)))
    monkeypatch.setattr(rpa, "interpret_flag", lambda: False)

    def sds(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    data = sds((hkv, pool, page, d), jnp.int8 if quantized else jnp.bfloat16)
    cache = (data, sds((hkv, pool), jnp.float32)) if quantized else data
    v_dim = shape.get("v_dim")  # the shared layout: no value pool
    q_block, ppb, slots = auto_ragged_blocks(
        head_dim=d, page_size=page, group=group,
        kv_itemsize=1 if quantized else 2, q_itemsize=2,
        quantized=quantized, kv_pools=1 if v_dim else 2)

    def step(q, k, v, tables, cu, q_lens, seq_lens, num_seqs):
        return rpa._ragged_attention(
            q, k, v, tables, cu, q_lens, seq_lens, num_seqs, None,
            rpa.DEFAULT_TOKEN_BLOCK, True, q_block, ppb, slots, v_dim)

    compiled = jax.jit(step).lower(
        sds((t, hkv * group, d), jnp.bfloat16), cache,
        None if v_dim else cache,
        sds((s_max, max_pages), jnp.int32), sds((s_max + 1,), jnp.int32),
        sds((s_max,), jnp.int32), sds((s_max,), jnp.int32),
        sds((1,), jnp.int32)).compile()
    assert "tpu_custom_call" in compiled.as_text()


# the decode entry (``paged_attention``: one token a segment through the
# ragged kernel) at the decode buckets the cells' configurations run
DECODES = {
    "thinker_bf16": dict(b=64, hkv=4, group=8),
    "thinker_8rows_bf16": dict(b=8, hkv=4, group=8),
    "thinker_int8": dict(b=64, hkv=4, group=8, quantized=True),
    "tp4_shard_bf16": dict(b=64, hkv=1, group=8),
    "group1_bf16": dict(b=64, hkv=4, group=1),
    "head256_bf16": dict(b=32, hkv=2, group=8, d=256, pool=32768,
                         max_pages=2080),
    "latent_bf16": dict(b=32, hkv=1, group=20, d=640, v_dim=512,
                        pool=16384, max_pages=1104),
}


@pytest.mark.parametrize("name", sorted(DECODES))
def test_decode_entry_compiles_for_v5e(name, one_chip, monkeypatch):
    """``paged_attention`` as ``forward_decode`` calls it (auto dispatch
    on a platform that compiles the kernels): one attention custom call,
    and nothing of the pool's size beside it."""
    import re

    from vllm_omni_tpu.ops import _dispatch
    shape = DECODES[name]
    b, hkv, group = (shape[k] for k in ("b", "hkv", "group"))
    d, pool, max_pages = (shape.get(k, v) for k, v in (
        ("d", 128), ("pool", 8192), ("max_pages", 528)))
    quantized, v_dim = shape.get("quantized", False), shape.get("v_dim")
    monkeypatch.setattr(rpa, "interpret_flag", lambda: False)
    monkeypatch.setattr(_dispatch, "pallas_mode", lambda: "native")

    def sds(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    data = sds((hkv, pool, 16, d), jnp.int8 if quantized else jnp.bfloat16)
    cache = (data, sds((hkv, pool), jnp.float32)) if quantized else data
    text = jax.jit(lambda q, k, v, tables, lens: rpa.paged_attention(
        q, k, v, tables, lens, v_dim=v_dim)).lower(
        sds((b, hkv * group, d), jnp.bfloat16), cache,
        None if v_dim else cache, sds((b, max_pages), jnp.int32),
        sds((b,), jnp.int32)).compile().as_text()
    assert text.count("tpu_custom_call") == 1
    assert "_ragged_attention" in text  # what ``dev_attn_share_pct`` finds
    moved = [m.group(0) for m in re.finditer(
        r"= \w+\[([\d,]+)\]\S* (copy|transpose|convert|gather)\(", text)
        if np.prod([int(x) for x in m.group(1).split(",")])
        >= hkv * pool * 16 * d]
    assert not moved, moved


def test_decode_step_holds_one_attention_call_a_layer(one_chip, monkeypatch):
    """A whole pure-decode step of the thinker at its published widths
    (two layers, 64 rows, the cell's pools donated) compiled for a v5e:
    one ``_ragged_attention`` custom call a layer (the name the
    benchmark's ``dev_attn_share_pct`` looks for), and no operation of a
    pool's size."""
    import functools
    import re

    from benchmark import spec
    from benchmark.archs import qwen3_moe as arch
    from vllm_omni_tpu.models.common import transformer
    from vllm_omni_tpu.ops import _dispatch

    pa = importlib.import_module("vllm_omni_tpu.ops.paged_attention")
    monkeypatch.setattr(rpa, "interpret_flag", lambda: False)
    monkeypatch.setattr(pa, "interpret_flag", lambda: False)
    monkeypatch.setattr(_dispatch, "pallas_mode", lambda: "native")
    layers, b = 2, 64
    config = dict(spec.load_json(
        spec.ROOT / "benchmark/configs/thinker-l8.json"),
        num_hidden_layers=layers)
    config.pop("program_config", None)  # the constant has the full depth
    cfg = arch.program_config(config)

    def sds(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    params = jax.tree.map(
        lambda x: sds(x.shape, x.dtype),
        jax.eval_shape(functools.partial(arch.init_weights, cfg=cfg,
                                         dtype=jnp.bfloat16),
                       jax.ShapeDtypeStruct((2,), jnp.uint32)))
    pool = sds((4, 8192, 16, 128), jnp.bfloat16)
    text = jax.jit(
        lambda p, tok, pos, kv, slots, tables, lens:
        transformer.forward_decode(p, cfg, tok, pos, kv, slots, tables, lens),
        donate_argnums=3).lower(
        params, sds((b,), jnp.int32), sds((b, 3), jnp.int32),
        [(pool, pool)] * layers, sds((b,), jnp.int32),
        sds((b, 528), jnp.int32), sds((b,), jnp.int32)).compile().as_text()
    calls = re.findall(r"%(\w+?)[.\d]* = [^\n]*custom-call\([^\n]*"
                       r"tpu_custom_call", text)
    assert sum("attention" in name for name in calls) == layers
    assert calls.count("_ragged_attention") == layers
    moved = [m.group(0) for m in re.finditer(
        r"= \w+\[([\d,]+)\]\S* (copy|copy-start|transpose|convert|gather)\(",
        text) if np.prod([int(x) for x in m.group(1).split(",")])
        >= 4 * 8192 * 16 * 128]
    assert not moved, moved


def test_latent_write_compiles_for_v5e(one_chip, monkeypatch):
    """The page write at the latent cache's shapes (one pool of 640-lane
    rows; a 2048-row step and a 32-row decode batch), and no operation of
    the pool's size in either."""
    import re

    pa = importlib.import_module("vllm_omni_tpu.ops.paged_attention")
    from vllm_omni_tpu.ops import _dispatch
    monkeypatch.setattr(pa, "interpret_flag", lambda: False)
    monkeypatch.setattr(_dispatch, "pallas_mode", lambda: "native")

    def sds(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    pool = sds((1, 16384, 16, 640), jnp.bfloat16)
    for rows in (2048, 32):
        text = jax.jit(
            lambda k, new, slots: pa.write_kv_cache(k, None, new, None,
                                                    slots)[0],
            donate_argnums=0).lower(
            pool, sds((rows, 1, 640), jnp.bfloat16),
            sds((rows,), jnp.int32)).compile().as_text()
        # the kernel for a chunk, the row scatter for a decode batch
        assert ("tpu_custom_call" in text) == (rows > 256)
        # nothing but the aliased operand and result is as large as the pool
        copies = [ln for ln in text.splitlines()
                  if re.search(r"= bf16\[1,16384,16,640\]\S* (copy|transpose)\(",
                               ln)]
        assert not copies, copies


def test_glm_weights_are_made_an_expert_at_a_time(one_chip):
    """The benchmark's GLM-4.7-Flash weights (the program's draw, the
    routed experts then related to one another) are made in one jitted
    call beside 10.3 GB of results: relating every layer's experts at once
    in float32 took 8 GB of temporaries and did not load on the chip (my
    chip run, PR 32); an expert at a time takes the largest drawn tensor's
    0.8 GB."""
    import functools

    from benchmark import spec
    from benchmark.archs import glm4_moe_lite as arch

    config = spec.load_json(
        spec.ROOT / "benchmark/configs/glm47flash-l8.json")
    cfg = arch.program_config(config)
    init = functools.partial(arch.init_weights, cfg=cfg, dtype=jnp.bfloat16)
    memory = jax.jit(init).lower(jax.ShapeDtypeStruct(
        (2,), jnp.uint32, sharding=one_chip)).compile().memory_analysis()
    assert 10.2e9 < memory.output_size_in_bytes < 10.5e9
    assert memory.temp_size_in_bytes < 1.5e9


def test_gated_delta_net_compiles_for_v5e(one_chip):
    """The Gated DeltaNet mixer of ops/gated_delta_rule.py (plain
    jax.numpy: the convolution, the chunked rule with its triangular solve
    and scan, the one-token form) at Qwen3-Next's widths: what the TPU's
    compiler would refuse (a solve or a scan it cannot lower, too much
    memory) fails here."""
    from vllm_omni_tpu.ops import gated_delta_rule as gdr

    heads, conv_dim, s_max = (16, 32, 128, 128), 8192, 32

    def sds(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    def states(rows):
        return (sds((rows, 3, conv_dim), jnp.bfloat16),
                sds((rows, 32, 128, 128), jnp.float32))

    def mixed(rows):
        return (sds((rows, conv_dim), jnp.bfloat16),
                sds((rows, 32), jnp.float32), sds((rows, 32), jnp.float32),
                sds((4, conv_dim), jnp.bfloat16))

    ragged = jax.jit(
        lambda m, g, b, w, c, r, cu, ql: gdr.gated_delta_net_ragged(
            m, g, b, w, c, r, cu, ql, heads)).lower(
        *mixed(4096), *states(s_max), sds((s_max + 1,), jnp.int32),
        sds((s_max,), jnp.int32)).compile()
    # the chunks' tensors in float32: well inside what the step may take
    assert ragged.memory_analysis().temp_size_in_bytes < 3 * 2 ** 30
    jax.jit(lambda m, g, b, w, c, r: gdr.gated_delta_net_decode(
        m, g, b, w, c, r, heads)).lower(
        *mixed(s_max), *states(s_max)).compile()


def test_sampler_keeps_its_selection_a_selection_on_v5e(one_chip):
    """``sample_tokens`` at the cells' shape compiles for a v5e with its
    bounded selection as the TopK custom call and ONE sort, the
    fallback's (PR 29).  Without the optimization barrier behind
    ``lax.top_k`` this compiler expands the selection into a second sort
    of the whole vocabulary, because the fallback's predicate reads its
    output: 15 ms a step where the selection takes 2.6 (my chip run,
    PR 29); the numbers come from the chip, this holds the shape of the
    program.  (It lives here because TPU compiles stay in this file.)"""
    import re

    from vllm_omni_tpu.sample import sampler

    def sds(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    b, v = 64, 152064
    text = sampler.sample_tokens.lower(
        sds((b, v), jnp.float32), sds((b,), jnp.float32),
        sds((b,), jnp.int32), sds((b,), jnp.float32),
        sds((b, 2), jnp.uint32)).compile().as_text()
    assert text.count('custom_call_target="TopK"') == 1
    assert len(re.findall(r" sort\(", text)) == 1


# the pools of the benchmark's cells, a step's rows, and which write
KV_WRITES = {
    "thinker_decode64": dict(hkv=4, pool=8192, d=128, t=64),
    "thinker_chunk2048": dict(hkv=4, pool=8192, d=128, t=2048),
    "qwen3next_doc4096": dict(hkv=2, pool=32768, d=256, t=4096),
    "tp4_shard_decode64": dict(hkv=1, pool=8192, d=128, t=64),
    "one_row": dict(hkv=4, pool=8192, d=128, t=1),
    # the edges ``write_tiling_reason`` draws: the longest step whose
    # plans fit SMEM, and a page so large that a grid step takes 16 tokens
    "longest_step_32768": dict(hkv=4, pool=8192, d=128, t=32768),
    "block16_float32": dict(hkv=8, pool=1024, d=256, t=2048,
                            dtype=jnp.float32),
    # the XLA row scatter the int8 pool's rows and an untileable geometry
    # take: its window is one [D] row, so it needs no relayout either
    "row_scatter_chunk2048": dict(hkv=4, pool=8192, d=128, t=2048,
                                  kernel=False),
    "served_decode64": dict(hkv=4, pool=8192, d=128, t=64, kernel="served"),
    "served_mixed128": dict(hkv=4, pool=8192, d=128, t=128,
                            kernel="served"),
    "served_qwen3next_doc4096": dict(hkv=2, pool=32768, d=256, t=4096,
                                     kernel="served"),
    "served_qwen3next_decode32": dict(hkv=2, pool=32768, d=256, t=32,
                                      kernel="served"),
}


@pytest.mark.parametrize("name", sorted(KV_WRITES))
def test_kv_write_compiles_in_place_for_v5e(name, one_chip, monkeypatch):
    """A step's KV write at the cells' pools, the pools donated: the
    compiled program holds no operation of a pool's size but the write
    itself and no temporary of that size, and its outputs alias the
    pools.  Until PR 31 the scatter ran with the kv head as its window
    and XLA copied the whole pool (134 MB a half, 2.1 GB a step) into
    the scatter's layout and back, 13 ms of every step on the chip;
    this holds the shape of the program, the time comes from the chip
    (scripts/kv_write_sweep.py)."""
    import re

    shape = KV_WRITES[name]
    hkv, pool, d, t = (shape[k] for k in ("hkv", "pool", "d", "t"))
    pa = importlib.import_module("vllm_omni_tpu.ops.paged_attention")
    monkeypatch.setattr(pa, "interpret_flag", lambda: False)

    def sds(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    def rows_only(kc, vc, kn, vn, slots):
        return (pa._scatter_rows(kc, kn, slots),
                pa._scatter_rows(vc, vn, slots))

    # "served": as ``write_kv_cache`` dispatches on a platform that
    # compiles the kernel: the row scatter up to 256 rows a half
    kernel = shape.get("kernel", True)
    write = {True: pa._write_pages, False: rows_only}.get(
        kernel, pa.write_kv_cache)
    if kernel == "served":
        from vllm_omni_tpu.ops import _dispatch
        monkeypatch.setattr(_dispatch, "pallas_mode", lambda: "native")
        kernel = hkv * t > 256
    dtype = jnp.dtype(shape.get("dtype", jnp.bfloat16))
    cache = sds((hkv, pool, 16, d), dtype)
    assert kernel is not True or pa.write_tiling_reason(cache, t) == ""
    new = sds((t, hkv, d), jnp.bfloat16)
    compiled = jax.jit(write, donate_argnums=(0, 1)).lower(
        cache, cache, new, new, sds((t,), jnp.int32)).compile()
    text = compiled.as_text()
    assert ("tpu_custom_call" in text) == kernel
    pool_elems = hkv * pool * 16 * d
    moved = [m.group(0) for m in re.finditer(
        r"= \w+\[([\d,]+)\]\S* (copy|copy-start|transpose|convert)\(", text)
        if np.prod([int(x) for x in m.group(1).split(",")]) >= pool_elems]
    assert not moved, moved
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes == 2 * pool_elems * dtype.itemsize
    # (the step's own rows, K and V, laid out for the kernel and padded)
    rows_bytes = 2 * (t + 128) * hkv * d * dtype.itemsize
    assert memory.temp_size_in_bytes < (
        pool_elems * dtype.itemsize // 16 + 2 * rows_bytes)
