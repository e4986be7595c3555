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
    # PR 49: a grid cell holds every kv head, and asks Mosaic for nothing
    # where it compiles so: granite's 8 kv heads (14.5 MB counted) and
    # Nemotron's 2 at a group of 16
    "granite_8kv_bf16": dict(t=4096, hkv=8, group=4, quantized=False),
    "granite_8kv_int8": dict(t=4096, hkv=8, group=4, quantized=True),
    "nemotron_2kv_bf16": dict(t=4096, hkv=2, group=16, quantized=False),
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
        quantized=quantized, kv_pools=1 if v_dim else 2, kv_heads=hkv)

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
    "lfm2_256rows_bf16": dict(b=256, hkv=4, group=8),
    "granite_8kv_bf16": dict(b=128, hkv=8, group=4),
    "granite_8kv_int8": dict(b=128, hkv=8, group=4, quantized=True),
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


@pytest.mark.parametrize("entry", ["mixed", "decode"])
def test_heads_of_64_compile_for_v5e(entry, one_chip, monkeypatch):
    """LFM2-8B-A1B's attention through both public entries, auto dispatch
    on a platform that compiles the kernels: 32 query and 8 kv heads of 64
    over pools of two heads a row ([4, pages, 16, 128]), a 4 096-row mixed
    step and the 256-row decode batch.  The kernel it lowers to is the
    thinker's (a head of 128, a group of 8); nothing of the pool's size is
    moved to widen the queries or to keep the outputs' own half."""
    import re

    from vllm_omni_tpu.ops import _dispatch
    monkeypatch.setattr(rpa, "interpret_flag", lambda: False)
    monkeypatch.setattr(_dispatch, "pallas_mode", lambda: "native")
    pool, max_pages, seqs = 32768, 400, 256

    def sds(dims, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    cache = sds((4, pool, 16, 128), jnp.bfloat16)
    if entry == "decode":
        text = jax.jit(rpa.paged_attention).lower(
            sds((seqs, 32, 64), jnp.bfloat16), cache, cache,
            sds((seqs, max_pages)), sds((seqs,))).compile().as_text()
    else:
        text = jax.jit(rpa.ragged_paged_attention).lower(
            sds((4096, 32, 64), jnp.bfloat16), cache, cache,
            sds((seqs, max_pages)), sds((seqs + 1,)), sds((seqs,)),
            sds((seqs,)), sds((1,))).compile().as_text()
    assert text.count("tpu_custom_call") == 1
    assert "_ragged_attention" in text
    moved = [m.group(0) for m in re.finditer(
        r"= \w+\[([\d,]+)\]\S* (copy|transpose|convert|gather)\(", text)
        if np.prod([int(x) for x in m.group(1).split(",")])
        >= 4 * pool * 16 * 128]
    assert not moved, moved
    assert not _dispatch.native_fallbacks


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

    from vllm_omni_tpu.ops import grouped_matmul

    pa = importlib.import_module("vllm_omni_tpu.ops.paged_attention")
    for module in (rpa, pa, grouped_matmul):
        monkeypatch.setattr(module, "interpret_flag", lambda: False)
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


@pytest.mark.parametrize("t", [2048, 4096])
def test_gated_delta_rule_kernel_compiles_for_v5e(t, one_chip, monkeypatch):
    """The chunked rule forced through its kernel at Qwen3-Next's widths
    (32 value heads of 128 x 128, 32 sequences): ONE Mosaic call holds the
    products, the inverse and the state's update, so the compiled text has
    no triangular-solve custom call and no loop, and the chunks' tensors
    outside the kernel stay far under what the ``jax.numpy`` form keeps."""
    from vllm_omni_tpu.ops import gated_delta_rule as gdr

    monkeypatch.setattr(gdr, "interpret_flag", lambda: False)
    s_max, hv, d = 32, 32, 128

    def sds(dims, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    assert not gdr.kernel_reason(sds((t, hv, d)), sds((t, hv, d)),
                                 sds((s_max, hv, d, d)))
    compiled = jax.jit(
        lambda *a: gdr.gated_delta_rule_ragged(*a, use_pallas=True)).lower(
        sds((t, hv, d)), sds((t, hv, d)), sds((t, hv, d)), sds((t, hv)),
        sds((t, hv)), sds((s_max, hv, d, d)), sds((s_max + 1,), jnp.int32),
        sds((s_max,), jnp.int32)).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 1
    assert "triangular" not in text.lower()
    assert " while(" not in text
    assert compiled.memory_analysis().temp_size_in_bytes < 1e9


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


# ------------------------- a window and a selection (ISSUE 34), and what
# they leave alone
BOUNDED = {
    # dots3-note-prev's sliding layers: 64 heads on a 1 152-lane row whose
    # first 1 024 lanes are the value, window 513, the window kind's pool
    "window": dict(group=64, d=1152, v_dim=1024, pool=5155, window=513),
    # its full layers: 128 heads on GLM's 640-lane row, a selection
    "keep": dict(group=128, d=640, v_dim=512, pool=16384, keep=True),
}


@pytest.mark.parametrize("entry", ["mixed", "decode"])
@pytest.mark.parametrize("name", sorted(BOUNDED))
def test_bounded_attention_compiles_for_v5e(name, entry, one_chip,
                                            monkeypatch):
    """The kernel with ``window=513`` and with a ``keep`` operand at the
    model's shapes (a 2 048-token mixed step and the 32-row decode entry,
    contexts to 17 664): one custom call, which asks Mosaic for the VMEM
    its 1 024-row tile needs, and whole 512-key blocks."""
    from vllm_omni_tpu.ops import _dispatch
    from vllm_omni_tpu.ops.autotune import ragged_vmem_limit
    shape = BOUNDED[name]
    group, d, v_dim, pool = (shape[k] for k in ("group", "d", "v_dim",
                                                "pool"))
    s_max, max_pages, t = 32, 1104, 2048
    monkeypatch.setattr(rpa, "interpret_flag", lambda: False)
    monkeypatch.setattr(_dispatch, "pallas_mode", lambda: "native")
    kw = dict(scale=0.1, v_dim=v_dim, window=shape.get("window"))

    def sds(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    assert auto_ragged_blocks(head_dim=d, page_size=16, group=group,
                              kv_itemsize=2, q_itemsize=2,
                              kv_pools=1) == (8, 32, 2)
    assert ragged_vmem_limit(d, 16, group, 2, 2, False, 1) > 8 * 2 ** 20
    cache = sds((1, pool, 16, d), jnp.bfloat16)
    rows = t if entry == "mixed" else s_max
    keep = (sds((rows, max_pages * 16), jnp.bool_),) if shape.get("keep") \
        else ()
    if entry == "mixed":
        def step(q, k, tables, cu, q_lens, seq_lens, num_seqs, *keep):
            return rpa.ragged_paged_attention(
                q, k, None, tables, cu, q_lens, seq_lens, num_seqs,
                keep=keep[0] if keep else None, **kw)
        args = (sds((t, group, d), jnp.bfloat16), cache,
                sds((s_max, max_pages), jnp.int32),
                sds((s_max + 1,), jnp.int32), sds((s_max,), jnp.int32),
                sds((s_max,), jnp.int32), sds((1,), jnp.int32))
    else:
        def step(q, k, tables, lens, *keep):
            return rpa.paged_attention(q, k, None, tables, lens,
                                       keep=keep[0] if keep else None, **kw)
        args = (sds((s_max, group, d), jnp.bfloat16), cache,
                sds((s_max, max_pages), jnp.int32), sds((s_max,), jnp.int32))
    text = jax.jit(step).lower(*args, *keep).compile().as_text()
    assert text.count("tpu_custom_call") == 1


def test_indexer_compiles_for_v5e(one_chip, monkeypatch):
    """The indexer's score kernel (64 heads x 128 over the gathered index
    keys) and the selection by counting passes at the model's shapes, for
    a 2 048-token step and for 32 decode rows: one custom call each, and
    no sort of ``[T, L]``."""
    ix = importlib.import_module("vllm_omni_tpu.ops.indexer")
    from vllm_omni_tpu.ops import _dispatch
    monkeypatch.setattr(ix, "interpret_flag", lambda: False)
    monkeypatch.setattr(_dispatch, "pallas_mode", lambda: "native")

    def sds(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    pool = sds((1, 16384, 16, 128), jnp.bfloat16)
    mixed = jax.jit(lambda q, w, p, tables, cu, q_lens, seq_lens, n:
                    ix.topk_keep(ix.indexer_scores(
                        q, w, p, tables, cu, q_lens, seq_lens, n), 2048)
                    ).lower(
        sds((2048, 64, 128), jnp.bfloat16), sds((2048, 64), jnp.float32),
        pool, sds((32, 1104), jnp.int32), sds((33,), jnp.int32),
        sds((32,), jnp.int32), sds((32,), jnp.int32),
        sds((1,), jnp.int32)).compile()
    decode = jax.jit(lambda q, w, p, tables, lens: ix.topk_keep(
        ix.indexer_scores_decode(q, w, p, tables, lens), 2048)).lower(
        sds((32, 64, 128), jnp.bfloat16), sds((32, 64), jnp.float32), pool,
        sds((32, 1104), jnp.int32), sds((32,), jnp.int32)).compile()
    for compiled in (mixed, decode):
        text = compiled.as_text()
        assert text.count("tpu_custom_call") == 1
        assert " sort(" not in text
    # the step's scores, their unsigned image and the tie ranks, a block
    # of rows at a time: well under a GB beside the weights
    assert mixed.memory_analysis().temp_size_in_bytes < 0.6e9


@pytest.mark.parametrize("max_model_len", [2048, 17664])
def test_tables_within_index_topk_skip_the_selection_on_v5e(
        max_model_len, one_chip, monkeypatch):
    """A 2 048-token mixed step of dots3-note-prev's leading full layer and
    its first sliding one at their published widths, compiled for a v5e
    under the tables of two deployments: where ``max_model_len`` is at most
    ``index_topk`` no row can see more keys than the selection keeps, and
    the step holds the index key's write but neither the indexer's score
    kernel nor a ``keep`` operand (the 128-head walk alone); past it, the
    score kernel is there once a full layer.  Both fit beside the weights
    (the branch that skips never ran to its end on the chip in PR 34's
    first calls, for a reason that was not the compiler's)."""
    import functools
    import re

    from benchmark import spec
    from benchmark.archs import dots3_note as arch
    from vllm_omni_tpu.models.common import transformer
    from vllm_omni_tpu.ops import _dispatch

    ix = importlib.import_module("vllm_omni_tpu.ops.indexer")
    pa = importlib.import_module("vllm_omni_tpu.ops.paged_attention")
    for module in (rpa, pa, ix):
        monkeypatch.setattr(module, "interpret_flag", lambda: False)
    monkeypatch.setattr(_dispatch, "pallas_mode", lambda: "native")
    config = spec.load_json(
        spec.ROOT / "benchmark/configs/dots3note-l5-ep8.json")
    config.update(num_hidden_layers=3,
                  layer_types=config["layer_types"][:3],
                  stage_layers={"dense_full": 1, "sparse_full": 1,
                                "sparse_sliding": 1})
    cfg = arch.program_config(config)

    def sds(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    params = jax.tree.map(
        lambda x: sds(x.shape, x.dtype),
        jax.eval_shape(functools.partial(arch.init_weights, cfg=cfg,
                                         dtype=jnp.bfloat16),
                       jax.ShapeDtypeStruct((2,), jnp.uint32)))
    t, s_max, pages = 2048, 32, max_model_len // 16
    full = (sds((1, 16384, 16, 640), jnp.bfloat16), None,
            sds((1, 16384, 16, 128), jnp.bfloat16))
    slide = (sds((1, 5155, 16, 1152), jnp.bfloat16), None)
    i32 = jnp.int32
    compiled = jax.jit(
        lambda p, tok, pos, kv, slots, tables, lens, cu, q_lens, n, wslots,
        wtables: transformer.forward_unified(
            p, cfg, tok, pos, kv, slots, tables, lens, cu, q_lens, n,
            window_pages=(wslots, wtables)),
        donate_argnums=3).lower(
        params, sds((t,), i32), sds((t,), i32), [full, full, slide],
        sds((t,), i32), sds((s_max, pages), i32), sds((s_max,), i32),
        sds((s_max + 1,), i32), sds((s_max,), i32), sds((1,), i32),
        sds((t,), i32), sds((s_max, pages), i32)).compile()
    calls = re.findall(r"%(\w+?)[.\d]* = [^\n]*custom-call\([^\n]*"
                       r"tpu_custom_call", compiled.as_text())
    assert sum("attention" in name for name in calls) == 3
    assert sum("indexer" in name for name in calls) == (
        2 if max_model_len > cfg.index_topk else 0)
    assert compiled.memory_analysis().temp_size_in_bytes < 1.2e9


#: sha256[:16] of ``str(jax.make_jaxpr(...))`` of the decode entry and of a
#: 2 048-token mixed step at the thinker's and at GLM-4.7-Flash's shapes,
#: taken on the parent of PR 34 (628fc51): the programs a window and a
#: selection must leave as they were.  (A jaxpr, not the compiled text:
#: that holds the kernel's source lines, which move with every edit of
#: its file.  A PR that means to change the kernel for these models
#: replaces the digests and says so.)
#: PR 49 means to, and replaced all four: the grid lost its kv-head axis
#: (a grid cell holds a q block's rows of every kv head, a KV block comes
#: in by one strided copy a page and the heads attend it in a loop), so
#: the thinker's two programs changed by design, and GLM's one latent kv
#: head traces through the same code (a 1-D grid, ``pool[:, page_id]``
#: copies, no loop and no scratch for its running max and sum); on the
#: chip GLM's and dots3's calls cost what they did (PERF.md, PR 49).
UNBOUNDED = {
    "thinker": (dict(b=64, hkv=4, group=8, d=128, v_dim=None, pool=8192,
                     max_pages=528),
                ("7a3945571d04373c", "8bc93ee618bcd74d")),
    "glm": (dict(b=32, hkv=1, group=20, d=640, v_dim=512, pool=16384,
                 max_pages=1104),
            ("92bfb0859447b173", "4a0580af12d3af69")),
}


@pytest.mark.parametrize("name", sorted(UNBOUNDED))
def test_without_a_window_or_a_selection_the_kernel_is_the_parents(
        name, monkeypatch):
    import hashlib

    shape, want = UNBOUNDED[name]
    b, hkv, group, d, v_dim, pool, max_pages = (
        shape[k] for k in ("b", "hkv", "group", "d", "v_dim", "pool",
                           "max_pages"))
    monkeypatch.setattr(rpa, "interpret_flag", lambda: False)

    def sds(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype)

    cache = sds((hkv, pool, 16, d), jnp.bfloat16)
    value = None if v_dim else cache
    decode = jax.make_jaxpr(lambda q, k, v, tables, lens: rpa.paged_attention(
        q, k, v, tables, lens, use_pallas=True, v_dim=v_dim))(
        sds((b, hkv * group, d), jnp.bfloat16), cache, value,
        sds((b, max_pages), jnp.int32), sds((b,), jnp.int32))
    mixed = jax.make_jaxpr(
        lambda q, k, v, tables, cu, q_lens, seq_lens:
        rpa.ragged_paged_attention(q, k, v, tables, cu, q_lens, seq_lens, 3,
                                   use_pallas=True, v_dim=v_dim))(
        sds((2048, hkv * group, d), jnp.bfloat16), cache, value,
        sds((32, max_pages), jnp.int32), sds((33,), jnp.int32),
        sds((32,), jnp.int32), sds((32,), jnp.int32))
    got = tuple(hashlib.sha256(str(x).encode()).hexdigest()[:16]
                for x in (decode, mixed))
    assert got == want


#: the same of the WHOLE decode step and of a 32-token mixed step at the
#: tiny widths of the three families the benchmark served before PR 34
#: (grouped-query attention with experts, GLM's latent attention,
#: Qwen3-Next's hybrid stack), taken on 628fc51 as these tests trace them:
#: layer kinds as data, a ``LatentKind`` a kind, the rescale, the head
#: gate, the indexer and the second table leave their programs as they were.
#: Taken again at PR 43, which changes every expert layer's program by
#: design (``ops/moe.py _combine``: a gather by the sort's inverse and a
#: float32 sum over a token's picks where a scatter-add stood).  The two
#: other families with state slots (LFM2's short convolutions, granite's
#: Mamba-2 layers) were taken on 7d8562f, the parent of PR 45, which moved
#: what is particular to a kind of state into ``models/common/state_layers``
#: and had to leave all five as they were.
STEPS = {
    "gqa": ("b96834a68e30334f", "2f7ce95ab51fe417"),
    "glm": ("7bc4f27799f00ede", "e825658d5b3707a0"),
    "next": ("ce74e8f7b40a9a44", "596873d85deaebd2"),
    "lfm2": ("76734f29bf479e01", "958cd578f3e006b8"),
    "granite": ("4e5cb09e880f7e37", "f348acc2eb07471f"),
}


@pytest.mark.parametrize("name", sorted(STEPS))
def test_without_a_window_or_a_selection_the_steps_are_the_parents(name):
    import functools
    import hashlib

    from vllm_omni_tpu.models.common import transformer as tfm
    from vllm_omni_tpu.models.glm4_moe_lite import tiny_config as glm_tiny
    from vllm_omni_tpu.models.granite_moe_hybrid import (
        tiny_config as granite_tiny,
    )
    from vllm_omni_tpu.models.lfm2_moe import tiny_config as lfm2_tiny
    from vllm_omni_tpu.models.qwen3_next import tiny_config as next_tiny

    cfg = {"gqa": tfm.TransformerConfig.tiny_moe, "glm": glm_tiny,
           "next": next_tiny, "lfm2": lfm2_tiny,
           "granite": granite_tiny}[name]()
    i32, f32 = jnp.int32, jnp.float32

    def sds(dims, dtype=i32):
        return jax.ShapeDtypeStruct(dims, dtype)

    params = jax.eval_shape(functools.partial(
        tfm.init_params, cfg=cfg, dtype=f32), jax.random.PRNGKey(0))
    lay = cfg.kv_layout
    pool = sds((lay.heads, 32, 16, lay.width), f32)
    kv = [(pool, None if lay.pools == 1 else pool)] * cfg.num_kv_layers
    extra = {}
    if cfg.num_state_layers:
        # a layer's slots as the cache builds them: an array a shape of
        # what a sequence carries through a layer of that kind
        kv = tfm.HybridCaches(kv, [
            tuple(sds((4, *shape), f32) for shape in cfg.state_shapes(kind))
            for kind in cfg.layer_types if kind in tfm.STATE_KINDS])
        extra = {"state_slots": sds((4,))}
    decode = jax.make_jaxpr(
        lambda p, kv, tok, pos, slots, tables, ctx, **e: tfm.forward_decode(
            p, cfg, tok, pos, kv, slots, tables, ctx, **e))(
        params, kv, sds((4,)), sds((4,)), sds((4,)), sds((4, 8)), sds((4,)),
        **extra)
    mixed = jax.make_jaxpr(
        lambda p, kv, tok, pos, slots, tables, lens, cu, q_lens, n, **e:
        tfm.forward_unified(p, cfg, tok, pos, kv, slots, tables, lens, cu,
                            q_lens, n, **e))(
        params, kv, sds((32,)), sds((32,)), sds((32,)), sds((4, 8)),
        sds((4,)), sds((5,)), sds((4,)), sds((1,)), **extra)
    got = tuple(hashlib.sha256(str(x).encode()).hexdigest()[:16]
                for x in (decode, mixed))
    assert got == STEPS[name]


#: the same of the cache-less full-sequence forward (two sequences of 16
#: rows) of the three families whose layers carry a state, taken on 7d8562f:
#: PR 45 made its three hand-written closures one, built from the kind's
#: record, and had to leave the programs as they were.
HIDDEN = {
    "next": "266fb2821e3573f6",
    "lfm2": "2cbd476190591485",
    "granite": "db84a3ded61c833b",
}


@pytest.mark.parametrize("name", sorted(HIDDEN))
def test_the_cache_less_forward_is_the_parents(name):
    import functools
    import hashlib
    import importlib

    from vllm_omni_tpu.models.common import transformer as tfm

    module = {"next": "qwen3_next", "lfm2": "lfm2_moe",
              "granite": "granite_moe_hybrid"}[name]
    cfg = importlib.import_module(
        f"vllm_omni_tpu.models.{module}").tiny_config()
    params = jax.eval_shape(functools.partial(
        tfm.init_params, cfg=cfg, dtype=jnp.float32), jax.random.PRNGKey(0))
    hidden = jax.make_jaxpr(
        lambda p, tok: tfm.forward_hidden(p, cfg, tok))(
        params, jax.ShapeDtypeStruct((2, 16), jnp.int32))
    assert hashlib.sha256(str(hidden).encode()).hexdigest()[:16] \
        == HIDDEN[name]


def test_dots3_weights_are_made_an_expert_at_a_time(one_chip):
    """The benchmark's dots3-note-prev weights (the program's draw, the
    routed experts related an expert at a time, the latent norms' weights
    at their power) are made in one jitted call beside 10.9 GB of results
    with under 1.5 GB of temporaries: scaling every layer's experts after
    the draw took a second copy of them and did not load on the chip (my
    chip run, PR 34: the set-up then hung)."""
    import functools

    from benchmark import spec
    from benchmark.archs import dots3_note as arch

    config = spec.load_json(
        spec.ROOT / "benchmark/configs/dots3note-l5-ep8.json")
    cfg = arch.program_config(config)
    init = functools.partial(arch.init_weights, cfg=cfg, dtype=jnp.bfloat16)
    memory = jax.jit(init).lower(jax.ShapeDtypeStruct(
        (2,), jnp.uint32, sharding=one_chip)).compile().memory_analysis()
    assert 10.8e9 < memory.output_size_in_bytes < 11.0e9
    assert memory.temp_size_in_bytes < 1.5e9


SSD_DIMS = (128, 64, 128)  # granite-4.0-h-small: heads, head width, state


def _no_pool_copies(compiled, slots: int) -> None:
    """The compiled text holds no copy of the state pool: the kernel's
    alias and the loop's dynamic-update keep it where it lies."""
    h, p, n = SSD_DIMS
    pool = f"f32[{slots},{h},{p},{n}]"
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert not [ln for ln in text.splitlines()
                if pool in ln.split(" = ")[-1].split("(")[0]
                and (" copy(" in ln or "copy-start" in ln)]
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes >= slots * h * p * n * 4
    assert memory.temp_size_in_bytes < 1 * 2 ** 30


@pytest.mark.parametrize("entry", ["decode", "ragged"])
def test_state_space_rule_compiles_in_place_for_v5e(entry, one_chip,
                                                    monkeypatch):
    """ops/ssd.py at granite-4.0-h-small's widths, 128 slots (537 MB of
    pool a layer): the recurrent kernel over the slot ids (a [32, 64, 128]
    block found through a prefetched slot, lane-sliced columns broadcast
    along the lanes, the pool aliased in and out) and the ragged form (the
    same kernel for the one-token rows, then the loop over the chunks that
    exist) compile for a v5e with the pool donated and no operation that
    copies it."""
    from vllm_omni_tpu.ops import ssd

    monkeypatch.setattr(ssd, "interpret_flag", lambda: False)
    h, p, n = SSD_DIMS
    slots = 128

    def sds(dims, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    def rows(r):
        return (sds((r, h, p)), sds((r, h)), sds((r, h)), sds((r, n)),
                sds((r, n)), sds((slots, h, p, n)))

    if entry == "decode":
        compiled = jax.jit(
            lambda *a: ssd.ssd_decode(*a, use_pallas=True),
            donate_argnums=(5,)).lower(
            *rows(128), sds((128,), jnp.int32),
            sds((128,), jnp.bool_)).compile()
    else:
        compiled = jax.jit(
            lambda *a: ssd.ssd_ragged(*a, use_pallas=True),
            donate_argnums=(5,)).lower(
            *rows(2048), sds((128,), jnp.int32), sds((128,), jnp.bool_),
            sds((129,), jnp.int32), sds((128,), jnp.int32)).compile()
    _no_pool_copies(compiled, slots)


def test_granite_weights_are_made_beside_their_results(one_chip):
    """The benchmark's granite-4.0-h-small weights (the program's draw,
    the experts related an expert at a time, the four gains) are made in
    one jitted call beside 6.53 GB of results with under 1.5 GB of
    temporaries."""
    import functools

    from benchmark import spec
    from benchmark.archs import granite_moe_hybrid as arch

    config = spec.load_json(
        spec.ROOT / "benchmark/configs/granite4h-l10-ep4.json")
    cfg = arch.program_config(config)
    init = functools.partial(arch.init_weights, cfg=cfg, dtype=jnp.bfloat16)
    memory = jax.jit(init).lower(jax.ShapeDtypeStruct(
        (2,), jnp.uint32, sharding=one_chip)).compile().memory_analysis()
    assert 6.5e9 < memory.output_size_in_bytes < 6.56e9
    assert memory.temp_size_in_bytes < 1.5e9


# ------------------------------------------- NVIDIA-Nemotron-3-Super (PR 46)
@pytest.mark.parametrize("entry", ["decode", "ragged"])
def test_grouped_state_space_rule_compiles_in_place_for_v5e(entry, one_chip,
                                                            monkeypatch):
    """ops/ssd.py at Nemotron-3-Super's widths (the same 128 heads of 64
    on a state of 128, with EIGHT groups of B and C and chunks of 128):
    the recurrent kernel hands a block of 32 heads its two groups as a
    ``[2, 128]`` block of ``[R, 4, 2, 128]``, and the chunked form takes the
    heads a group at a time; both compile for a v5e with the pool donated
    and no operation that copies it."""
    from vllm_omni_tpu.ops import ssd

    monkeypatch.setattr(ssd, "interpret_flag", lambda: False)
    h, p, n = SSD_DIMS
    slots, groups = 128, 8

    def sds(dims, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    def rows(r):
        return (sds((r, h, p)), sds((r, h)), sds((r, h)),
                sds((r, groups, n)), sds((r, groups, n)),
                sds((slots, h, p, n)))

    if entry == "decode":
        compiled = jax.jit(
            lambda *a: ssd.ssd_decode(*a, use_pallas=True),
            donate_argnums=(5,)).lower(
            *rows(128), sds((128,), jnp.int32),
            sds((128,), jnp.bool_)).compile()
    else:
        compiled = jax.jit(
            lambda *a: ssd.ssd_ragged(*a, chunk=128, use_pallas=True),
            donate_argnums=(5,)).lower(
            *rows(2048), sds((128,), jnp.int32), sds((128,), jnp.bool_),
            sds((129,), jnp.int32), sds((128,), jnp.int32)).compile()
    _no_pool_copies(compiled, slots)


def test_nemotron_decode_step_compiles_for_v5e(one_chip, monkeypatch):
    """A whole pure-decode step of the benchmark's Nemotron-3-Super cut at
    its published widths (eleven layers of one sublayer each, 128 rows, the
    cell's pages and slots donated) compiles for a v5e: one
    ``_ragged_attention`` call (32 query heads on 2 kv heads: a group of
    16), one ``_recurrent_inplace`` a Mamba-2 layer, the experts' two
    grouped matmuls of each expert layer as the few-rows kernel (2 816
    pairs over a router of 512: 5.5 rows an expert), no kernel that fell
    back, and the arguments the issue counts (12.1 GB) beside under half a
    gigabyte of temporaries.  The weights are made in one jitted call
    beside their 9.30 GB with under 2.5 GB of temporaries (the calibration
    pass that balances the routers runs the float32 reference's layers on
    512 tokens in the same call)."""
    import functools
    import re

    from benchmark import spec
    from benchmark.archs import nemotron_h as arch
    from vllm_omni_tpu.models.common import transformer
    from vllm_omni_tpu.ops import _dispatch, grouped_matmul, ssd

    pa = importlib.import_module("vllm_omni_tpu.ops.paged_attention")
    for module in (rpa, pa, ssd, grouped_matmul):
        monkeypatch.setattr(module, "interpret_flag", lambda: False)
    monkeypatch.setattr(_dispatch, "pallas_mode", lambda: "native")
    config = spec.load_json(
        spec.ROOT / "benchmark/configs/nemotron3super-l11-ep4.json")
    cfg = arch.program_config(config)
    init = functools.partial(arch.init_weights, cfg=cfg, dtype=jnp.bfloat16)
    made = jax.jit(init).lower(jax.ShapeDtypeStruct(
        (2,), jnp.uint32, sharding=one_chip)).compile().memory_analysis()
    assert 9.29e9 < made.output_size_in_bytes < 9.31e9
    assert made.temp_size_in_bytes < 2.5e9

    def sds(dims, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    params = jax.tree.map(
        lambda x: sds(x.shape, x.dtype),
        jax.eval_shape(init, jax.ShapeDtypeStruct((2,), jnp.uint32)))
    b, slots = 128, config["engine"]["max_num_seqs"]
    lay = cfg.kv_layout
    pool = sds((lay.heads, config["engine"]["num_pages"], 16, lay.width),
               jnp.bfloat16)
    caches = transformer.HybridCaches(
        [(pool, pool)] * cfg.num_kv_layers,
        [tuple(sds((slots, *shape), jnp.float32)
               for shape in cfg.state_shapes(kind))
         for kind in cfg.layer_types if kind in transformer.STATE_KINDS])
    assert (len(caches.kv), len(caches.state)) == (1, 5)
    compiled = jax.jit(
        lambda p, tok, pos, kv, sl, tables, lens, ss:
        transformer.forward_decode(p, cfg, tok, pos, kv, sl, tables, lens,
                                   state_slots=ss),
        donate_argnums=3).lower(
        params, sds((b,)), sds((b,)), caches, sds((b,)), sds((b, 400)),
        sds((b,)), sds((b,))).compile()
    calls = re.findall(r"%(\w+?)[.\d]* = [^\n]*custom-call\([^\n]*"
                       r"tpu_custom_call", compiled.as_text())
    assert calls.count("_ragged_attention") == 1
    assert calls.count("_recurrent_inplace") == 5
    assert calls.count("grouped_matmul_128x1024x2688_rows") == 5
    assert calls.count("grouped_matmul_128x2688x1024_rows") == 5
    assert not _dispatch.native_fallbacks
    memory = compiled.memory_analysis()
    assert 12.0e9 < memory.argument_size_in_bytes < 12.2e9
    assert memory.temp_size_in_bytes < 0.5e9


#: the experts' few-rows kernel at the widths of the cells whose steps it
#: serves, at their decode steps and at the largest rows each keeps
#: resident: (P, router width, E, K, N) of the gate/up (or up) product,
#: the down product's (K, N) being (N / 2 or N, K), and which of the two
#: products take the kernel there
GROUPED = {
    "thinker_64_rows": (512, 128, 128, 2048, 1536, 768, "up down"),
    "thinker_256_rows": (2048, 128, 128, 2048, 1536, 768, "up down"),
    "thinker_512_rows": (4096, 128, 128, 2048, 1536, 768, "down"),
    "lfm2_256_rows": (1024, 32, 32, 2048, 3584, 1792, "up down"),
    "lfm2_512_rows": (2048, 32, 32, 2048, 3584, 1792, "up down"),
    "granite_128_rows": (1280, 72, 18, 4096, 1536, 768, "up down"),
    "granite_256_rows": (2560, 72, 18, 4096, 1536, 768, "up"),
    "nemotron_128_rows": (2816, 512, 128, 1024, 2688, 2688, "up down"),
    "qwen3next_256_rows": (2560, 512, 128, 2048, 1024, 512, "up down"),
    "qwen3next_512_rows": (5120, 512, 128, 2048, 1024, 512, "up"),
    "dots3_256_rows": (2048, 256, 32, 5120, 3072, 1536, "up down"),
    "glm_512_rows": (2048, 64, 64, 2048, 3072, 1536, "up down"),
    # under a row an expert the op keeps ragged_dot; forced, the kernel
    # pads the rows up to a window
    "forced_1_row": (8, 128, 128, 2048, 1536, 768, ""),
}


@pytest.mark.parametrize("name", sorted(GROUPED))
def test_grouped_matmul_kernel_compiles_for_v5e(name, one_chip, monkeypatch):
    """The products of an expert MLP that take the few-rows kernel, at the
    tiles ``tiles()`` picks: ONE Mosaic call each (the offsets and the
    block map beside it are a handful of scalar operations), and the VMEM
    it asks for is what ``choice`` counted and under the budget."""
    from vllm_omni_tpu.ops import grouped_matmul as gm

    monkeypatch.setattr(gm, "interpret_flag", lambda: False)
    p, width, e, k, n, inter, kernel = GROUPED[name]

    def sds(dims, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    for product, kk, nn in (("up", k, n), ("down", inter, k)):
        took = gm.choice(p, width, kk, nn) == "kernel"
        assert took == (product in kernel.split())
        if not took and not name.startswith("forced"):
            continue
        text = jax.jit(
            lambda r, w, s: gm.grouped_matmul(r, w, s, width, True)).lower(
            sds((p, kk)), sds((e, kk, nn)),
            sds((e,), jnp.int32)).compile().as_text()
        assert text.count("tpu_custom_call") == 1
        assert "ragged-dot" not in text


# ------------------ command-a-plus-05-2026 (ISSUE 50): a window over
# per-head pages at 8 kv heads x 16 query heads, a parallel block
@pytest.mark.parametrize("entry", ["mixed", "decode"])
def test_commandaplus_steps_compile_for_v5e(entry, one_chip, monkeypatch):
    """The benchmark's Command A+ cut at its published widths (one period:
    three sliding layers and a full one, 16 of 128 experts held, the whole
    262 144-row vocabulary) compiles for a v5e with the cell's two pools
    donated: a 2 048-token mixed step and the 24-row decode bucket, each
    one ``_ragged_attention`` call a layer (three of them told the
    window), the rotation as the half-split kernel on the sliding layers'
    q and k alone (the full layer rotates nothing), no kernel that fell
    back (what the attention kernel asks of Mosaic for VMEM, nothing, is
    held in ``tests/ops/test_window_per_head.py``); the arguments the issue
    counts (11.35 GB of weights, 1.07 + 1.82 GB of pages) beside well
    under a gigabyte of temporaries.  The weights are made in one jitted
    call beside their 11.35 GB with next to no temporaries."""
    import functools
    import re

    from benchmark import spec
    from benchmark.archs import cohere2_moe as arch
    from vllm_omni_tpu.models.common import transformer
    from vllm_omni_tpu.ops import _dispatch, grouped_matmul
    from vllm_omni_tpu.ops import rope as rope_ops

    pa = importlib.import_module("vllm_omni_tpu.ops.paged_attention")
    for module in (rpa, pa, grouped_matmul, rope_ops):
        monkeypatch.setattr(module, "interpret_flag", lambda: False)
    monkeypatch.setattr(_dispatch, "pallas_mode", lambda: "native")
    config = spec.load_json(
        spec.ROOT / "benchmark/configs/commandaplus-l4-ep8.json")
    cfg = arch.program_config(config)
    init = functools.partial(arch.init_weights, cfg=cfg, dtype=jnp.bfloat16)
    if entry == "mixed":
        made = jax.jit(init).lower(jax.ShapeDtypeStruct(
            (2,), jnp.uint32, sharding=one_chip)).compile().memory_analysis()
        assert 11.34e9 < made.output_size_in_bytes < 11.36e9
        assert made.temp_size_in_bytes < 0.5e9

    def sds(dims, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    params = jax.tree.map(
        lambda x: sds(x.shape, x.dtype),
        jax.eval_shape(init, jax.ShapeDtypeStruct((2,), jnp.uint32)))
    engine = config["engine"]
    seqs, page = engine["max_num_seqs"], engine["page_size"]
    max_pages = engine["max_model_len"] // page
    window_pages = -(-seqs * (cfg.sliding_window
                              + engine["max_num_batched_tokens"])
                     // page) + seqs + 1
    assert window_pages == 9241
    lay = cfg.kv_layout_of("sliding_attention")
    assert lay == cfg.kv_layout and (lay.heads, lay.width) == (8, 128)
    pools = {
        "full_attention": sds((8, engine["num_pages"], page, 128),
                              jnp.bfloat16),
        "sliding_attention": sds((8, window_pages, page, 128),
                                 jnp.bfloat16)}
    caches = [(pools[kind],) * 2 for kind in cfg.kv_layer_types]
    if entry == "mixed":
        t = engine["max_num_batched_tokens"]
        compiled = jax.jit(
            lambda p, tok, pos, kv, sl, tables, lens, cu, q_lens, n, wsl, wt:
            transformer.forward_unified(
                p, cfg, tok, pos, kv, sl, tables, lens, cu, q_lens, n,
                window_pages=(wsl, wt)), donate_argnums=3).lower(
            params, sds((t,)), sds((t,)), caches, sds((t,)),
            sds((seqs, max_pages)), sds((seqs,)), sds((seqs + 1,)),
            sds((seqs,)), sds((1,)), sds((t,)),
            sds((seqs, max_pages))).compile()
    else:
        compiled = jax.jit(
            lambda p, tok, pos, kv, sl, tables, lens, wsl, wt:
            transformer.forward_decode(p, cfg, tok, pos, kv, sl, tables,
                                       lens, window_pages=(wsl, wt)),
            donate_argnums=3).lower(
            params, sds((seqs,)), sds((seqs,)), caches, sds((seqs,)),
            sds((seqs, max_pages)), sds((seqs,)), sds((seqs,)),
            sds((seqs, max_pages))).compile()
    text = compiled.as_text()
    calls = re.findall(r"%(\w+?)[.\d]* = [^\n]*custom-call\([^\n]*"
                       r"tpu_custom_call", text)
    assert calls.count("_ragged_attention") == 4
    assert calls.count("_apply_rope") == 2 * 3
    if entry == "decode":
        # 24 x 8 picks over a router of 128: 1.5 rows an expert, the
        # few-rows kernel on the 16 held
        assert calls.count("grouped_matmul_16x4096x8192_rows") == 4
        assert calls.count("grouped_matmul_16x4096x4096_rows") == 4
    assert not _dispatch.native_fallbacks
    memory = compiled.memory_analysis()
    assert 14.2e9 < memory.argument_size_in_bytes < 14.3e9
    assert memory.temp_size_in_bytes < 0.8e9


# ------------------ MiMo-V2.5 (ISSUE 55): a sink in the softmax, keys of
# 192 in rows of 256 lanes beside values of 128, kv heads a kind of layer
@pytest.mark.parametrize("entry", ["mixed", "decode"])
def test_mimo_steps_compile_for_v5e(entry, one_chip, monkeypatch):
    """The benchmark's MiMo-V2.5 cut at its published widths (a full layer
    with the dense MLP, five sliding layers and a full one; 16 of 256
    experts held; the whole vocabulary) compiles for a v5e with the cell's
    pools donated: a 2 048-token mixed step and the 24-row decode bucket,
    each one ``_ragged_attention`` call a layer (five of them told the
    window and handed the sinks), K pools ``[Hkv, P, 16, 256]`` beside V
    pools ``[Hkv, P, 16, 128]`` with 8 kv heads under the window table and
    4 under the full one, the page write a pool at a time (K and V rows
    differ in width), no kernel that fell back and nothing asked of Mosaic
    for VMEM; the arguments the issue counts (9.05 GB of weights, 1.61 +
    1.62 GB of pages) beside well under a gigabyte of temporaries.  The
    weights are made in one jitted call beside their 9.05 GB."""
    import functools
    import re

    from benchmark import spec
    from benchmark.archs import mimo_v2 as arch
    from vllm_omni_tpu.models.common import transformer
    from vllm_omni_tpu.ops import _dispatch, autotune, grouped_matmul
    from vllm_omni_tpu.ops import rope as rope_ops

    pa = importlib.import_module("vllm_omni_tpu.ops.paged_attention")
    for module in (rpa, pa, grouped_matmul, rope_ops):
        monkeypatch.setattr(module, "interpret_flag", lambda: False)
    monkeypatch.setattr(_dispatch, "pallas_mode", lambda: "native")
    asked = []
    limit = autotune.ragged_vmem_limit
    monkeypatch.setattr(
        rpa, "ragged_vmem_limit",
        lambda *a, **k: asked.append(limit(*a, **k)) or asked[-1])
    config = spec.load_json(
        spec.ROOT / "benchmark/configs/mimov25-l7-ep16.json")
    cfg = arch.program_config(config)
    init = functools.partial(arch.init_weights, cfg=cfg, dtype=jnp.bfloat16)
    if entry == "mixed":
        made = jax.jit(init).lower(jax.ShapeDtypeStruct(
            (2,), jnp.uint32, sharding=one_chip)).compile().memory_analysis()
        assert 9.04e9 < made.output_size_in_bytes < 9.06e9
        assert made.temp_size_in_bytes < 0.5e9

    def sds(dims, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    params = jax.tree.map(
        lambda x: sds(x.shape, x.dtype),
        jax.eval_shape(init, jax.ShapeDtypeStruct((2,), jnp.uint32)))
    assert params["layers"][1]["sinks"].dtype == jnp.float32
    engine = config["engine"]
    seqs, page = engine["max_num_seqs"], engine["page_size"]
    max_pages = engine["max_model_len"] // page
    window_pages = -(-seqs * (cfg.sliding_window
                              + engine["max_num_batched_tokens"])
                     // page) + seqs + 1
    assert window_pages == 3289
    pages = {"full_attention": engine["num_pages"],
             "sliding_attention": window_pages}
    caches = []
    for kind in cfg.kv_layer_types:
        lay = cfg.kv_layout_of(kind)
        assert (lay.width, lay.used, lay.v_width) == (256, 192, 128)
        assert lay.heads == {"full_attention": 4, "sliding_attention": 8}[kind]
        caches.append(tuple(
            sds((lay.heads, pages[kind], page, width), jnp.bfloat16)
            for width in (lay.width, lay.v_width)))
    if entry == "mixed":
        t = engine["max_num_batched_tokens"]
        compiled = jax.jit(
            lambda p, tok, pos, kv, sl, tables, lens, cu, q_lens, n, wsl, wt:
            transformer.forward_unified(
                p, cfg, tok, pos, kv, sl, tables, lens, cu, q_lens, n,
                window_pages=(wsl, wt)), donate_argnums=3).lower(
            params, sds((t,)), sds((t,)), caches, sds((t,)),
            sds((seqs, max_pages)), sds((seqs,)), sds((seqs + 1,)),
            sds((seqs,)), sds((1,)), sds((t,)),
            sds((seqs, max_pages))).compile()
    else:
        compiled = jax.jit(
            lambda p, tok, pos, kv, sl, tables, lens, wsl, wt:
            transformer.forward_decode(p, cfg, tok, pos, kv, sl, tables,
                                       lens, window_pages=(wsl, wt)),
            donate_argnums=3).lower(
            params, sds((seqs,)), sds((seqs,)), caches, sds((seqs,)),
            sds((seqs, max_pages)), sds((seqs,)), sds((seqs,)),
            sds((seqs, max_pages))).compile()
    text = compiled.as_text()
    calls = re.findall(r"%(\w+?)[.\d]* = [^\n]*custom-call\([^\n]*"
                       r"tpu_custom_call", text)
    assert calls.count("_ragged_attention") == 7
    if entry == "mixed":
        # K and V rows differ in width: two page writes a layer
        assert calls.count("_write_pages") == 2 * 7
    assert not _dispatch.native_fallbacks
    assert asked and not any(asked)  # nothing asked of Mosaic
    memory = compiled.memory_analysis()
    # the untied head (1.25 GB) is no argument of the forward to the
    # hidden states: 9.05 - 1.25 + 1.61 + 1.62 GB
    assert 11.0e9 < memory.argument_size_in_bytes < 11.1e9
    assert memory.temp_size_in_bytes < 0.8e9


#: the whole decode step and a 32-token mixed step at the tiny widths of
#: the three served families that ``STEPS`` does not hold (two kinds of
#: page over latent rows with an indexer; a window over per-head pages
#: under a parallel block; a sublayer a layer with latent experts), and
#: the window kernel's two entries at the two window cells' real geometry,
#: taken on 4d69fda, the parent of PR 55: kv heads, a rotary base and a
#: sink a KIND of layer, a value narrower than its key, the value scale and
#: the kernels' ``sinks`` operand leave all ten programs as they were.
WINDOWED_STEPS = {
    "cohere2_moe": ("6af0ed4177ceeb33", "99877045cc27344c"),
    "dots3_note": ("30fae29c292e2459", "be6ff162bf1349a4"),
    "nemotron_h": ("e291af98b3f43461", "5bdb3d3827b8db25"),
}
WINDOWED_KERNELS = {
    "commandaplus": (dict(hkv=8, group=16, d=128, v_dim=None, window=4096,
                          pool=9241),
                     ("07806c483f817e35", "5a2fae752c48782b")),
    "dots3_window": (dict(hkv=1, group=128, d=1152, v_dim=1024, window=513,
                          pool=5155),
                     ("6f74a933fff9fdc2", "f8d632a9dd7cae4f")),
}


def _digests(*jaxprs):
    import hashlib

    return tuple(hashlib.sha256(str(x).encode()).hexdigest()[:16]
                 for x in jaxprs)


@pytest.mark.parametrize("name", sorted(WINDOWED_STEPS))
def test_kinds_of_heads_leave_the_other_models_steps_the_parents(name):
    import functools

    from vllm_omni_tpu.models.common import transformer as tfm

    cfg = importlib.import_module(
        f"vllm_omni_tpu.models.{name}").tiny_config()
    i32, f32 = jnp.int32, jnp.float32

    def sds(dims, dtype=i32):
        return jax.ShapeDtypeStruct(dims, dtype)

    params = jax.eval_shape(functools.partial(
        tfm.init_params, cfg=cfg, dtype=f32), jax.random.PRNGKey(0))
    kv = []
    for kind in cfg.kv_layer_types:
        lay = cfg.kv_layout_of(kind)
        pool = sds((lay.heads, 32, 16, lay.width), f32)
        entry = (pool, None if lay.pools == 1 else pool)
        if cfg.index_topk and kind == "full_attention":
            entry += (sds((1, 32, 16, cfg.index_head_dim), f32),)
        kv.append(entry)
    extra, decode_pages, mixed_pages = {}, {}, {}
    if cfg.num_state_layers:
        kv = tfm.HybridCaches(kv, [
            tuple(sds((4, *shape), f32) for shape in cfg.state_shapes(kind))
            for kind in cfg.layer_types if kind in tfm.STATE_KINDS])
        extra = {"state_slots": sds((4,))}
    if cfg.num_window_layers:
        decode_pages = {"window_pages": (sds((4,)), sds((4, 8)))}
        mixed_pages = {"window_pages": (sds((32,)), sds((4, 8)))}
    decode = jax.make_jaxpr(
        lambda p, kv, tok, pos, slots, tables, ctx, **e: tfm.forward_decode(
            p, cfg, tok, pos, kv, slots, tables, ctx, **e))(
        params, kv, sds((4,)), sds((4,)), sds((4,)), sds((4, 8)), sds((4,)),
        **extra, **decode_pages)
    mixed = jax.make_jaxpr(
        lambda p, kv, tok, pos, slots, tables, lens, cu, q_lens, n, **e:
        tfm.forward_unified(p, cfg, tok, pos, kv, slots, tables, lens, cu,
                            q_lens, n, **e))(
        params, kv, sds((32,)), sds((32,)), sds((32,)), sds((4, 8)),
        sds((4,)), sds((5,)), sds((4,)), sds((1,)), **extra, **mixed_pages)
    assert _digests(decode, mixed) == WINDOWED_STEPS[name]


@pytest.mark.parametrize("name", sorted(WINDOWED_KERNELS))
def test_without_sinks_the_window_kernel_is_the_parents(name, monkeypatch):
    shape, want = WINDOWED_KERNELS[name]
    hkv, group, d, v_dim, window, pool = (
        shape[k] for k in ("hkv", "group", "d", "v_dim", "window", "pool"))
    monkeypatch.setattr(rpa, "interpret_flag", lambda: False)

    def sds(dims, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(dims, dtype)

    cache = sds((hkv, pool, 16, d), jnp.bfloat16)
    value = None if v_dim else cache
    decode = jax.make_jaxpr(lambda q, k, v, tables, lens: rpa.paged_attention(
        q, k, v, tables, lens, use_pallas=True, v_dim=v_dim, window=window))(
        sds((24, hkv * group, d), jnp.bfloat16), cache, value,
        sds((24, 1104)), sds((24,)))
    mixed = jax.make_jaxpr(
        lambda q, k, v, tables, cu, q_lens, seq_lens:
        rpa.ragged_paged_attention(q, k, v, tables, cu, q_lens, seq_lens, 3,
                                   use_pallas=True, v_dim=v_dim,
                                   window=window))(
        sds((2048, hkv * group, d), jnp.bfloat16), cache, value,
        sds((24, 1104)), sds((25,)), sds((24,)), sds((24,)))
    assert _digests(decode, mixed) == want
