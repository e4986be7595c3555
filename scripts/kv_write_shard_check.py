"""Check on the chips the two paths of the KV write that no cell of the
benchmark runs (PERF.md section 6, PR 31): the four-chip ``shard_map``
path, one kv head a chip, and the int8 pool.

    chiprun --chips 4 -- python -m scripts.kv_write_shard_check --tp4
    chiprun -- python -m scripts.kv_write_shard_check --int8

``--tp4``: one step's writes as ``ARModelRunner`` wraps a step for tensor
parallelism (``shard_map`` over ``tp``, the pools sharded by kv head and
donated, the slot mapping replicated, ``check_vma=False``): 8 x (K, V)
pools ``[4, 8192, 16, 128]`` bf16 of random rows, a decode batch (the
row scatter a shard) and two buckets that take the Pallas page write.
Each result is compared with the row-by-row numpy reference over whole
pools, the compiled text is searched for an operation of a shard's pool
size, the outputs' buffers are compared with the inputs', and the step
is timed beside the write as it was until PR 31 (the scatter with the
head as its window, which at one head a shard never relaid anything
out).  ``--aot`` compiles the same programs for a described v5e 2x2 and
runs nothing.

``--int8``: the write cases of ``tests/ops/test_kv_quant.py`` run as they
are on the chip, then the int8 write at the thinker's pool is searched
for an operation of the pool's size and timed.
"""

import argparse
import json
import os
import statistics
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from scripts.kv_write_sweep import (
    CANDIDATES,
    PAGE,
    pool_sized_ops,
    reference,
    step_slots,
)
from vllm_omni_tpu.ops.paged_attention import init_kv_cache, write_kv_cache
from vllm_omni_tpu.parallel.sharding import AXIS_TP, ar_kv_cache_spec

LAYERS, HKV, PAGES, D = 8, 4, 8192, 128
STEPS = {"decode64": (0, 64, 64), "mixed512": (300, 60, 512),
         "doc4096": (2037, 11, 4096)}


def tp_step(mesh, write):
    """One step's writes under ``shard_map``, as the runner wraps them."""
    from jax import shard_map

    def step(caches, kn, vn, slots):
        return [write(kc, vc, kn, vn, slots) for kc, vc in caches]

    rows = P(None, AXIS_TP, None)
    kv = [ar_kv_cache_spec()] * LAYERS
    return jax.jit(
        shard_map(step, mesh=mesh, in_specs=(kv, rows, rows, P()),
                  out_specs=kv, check_vma=False),
        donate_argnums=(0,))


def pointers(caches):
    return [s.data.unsafe_buffer_pointer()
            for pair in caches for half in pair
            for s in half.addressable_shards]


def tp4(args):
    if args.aot:
        # dispatch as on the chip, with no chip attached
        import importlib

        from jax.experimental import topologies

        from vllm_omni_tpu.ops import _dispatch
        pa = importlib.import_module("vllm_omni_tpu.ops.paged_attention")
        _dispatch.pallas_mode = lambda: "native"
        pa.interpret_flag = lambda: False
        devices = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2").devices
    else:
        devices = jax.devices()
    mesh = Mesh(np.array(devices[:4]), (AXIS_TP,))
    pool_sh = NamedSharding(mesh, P(AXIS_TP, None, None, None))
    rows_sh = NamedSharding(mesh, P(None, AXIS_TP, None))
    rep = NamedSharding(mesh, P())
    shape = (HKV, PAGES, PAGE, D)
    shard_elems = int(np.prod(shape)) // 4
    rng = np.random.default_rng(3104)
    out = []
    if not args.aot:  # two layers' worth of random pools, used in turn
        host = [tuple(rng.standard_normal(shape, np.float32)
                      .astype(jnp.bfloat16) for _ in range(2))
                for _ in range(2)]
    for name, (prefill, decodes, t) in STEPS.items():
        slots = step_slots(rng, PAGES, prefill, decodes, t)
        for which in ("served", "flat_heads"):
            fn = tp_step(mesh, CANDIDATES[which])
            sds = jax.ShapeDtypeStruct
            pool = sds(shape, jnp.bfloat16, sharding=pool_sh)
            new = sds((t, HKV, D), jnp.bfloat16, sharding=rows_sh)
            compiled = fn.lower(
                [(pool, pool)] * LAYERS, new, new,
                sds((t,), jnp.int32, sharding=rep)).compile()
            text = compiled.as_text()
            row = dict(step=name, write=which,
                       kernel="tpu_custom_call" in text,
                       pool_sized_ops=pool_sized_ops(text, shard_elems))
            if args.aot:
                print(row, flush=True)
                continue
            caches = [tuple(jax.device_put(h, pool_sh)
                            for h in host[i % 2]) for i in range(LAYERS)]
            kn, vn = (jax.device_put(jnp.asarray(
                rng.standard_normal((t, HKV, D)), jnp.bfloat16), rows_sh)
                for _ in range(2))
            s = jax.device_put(jnp.asarray(slots), rep)
            before = pointers(caches)
            caches = compiled(caches, kn, vn, s)
            jax.block_until_ready(caches)
            row["in_place"] = pointers(caches) == before
            # the first and the last layer, whole pools
            for start, got in zip((host[0], host[(LAYERS - 1) % 2]),
                                  (caches[0], caches[-1])):
                for h, g, rows in zip(start, got, (kn, vn)):
                    if not np.array_equal(
                            np.asarray(g), reference(h, rows, slots)):
                        raise SystemExit(f"{name} {which}: differs from "
                                         "the row-by-row reference")
            row["equals_reference"] = True
            times = []
            for _ in range(args.reps):
                t0 = time.perf_counter()
                caches = compiled(caches, kn, vn, s)
                jax.block_until_ready(caches)
                times.append((time.perf_counter() - t0) * 1e3)
            row["ms_median"] = statistics.median(times)
            row["ms_min"] = min(times)
            del caches
            print(row, flush=True)
            out.append(row)
    return out


def int8(args):
    from tests.ops import test_kv_quant as cases
    ran = []
    for name in ("test_quant_write_roundtrips_within_half_step",
                 "test_quant_write_fresh_page_resets_stale_scale",
                 "test_quant_write_append_rescales_existing_tokens"):
        getattr(cases, name)()
        ran.append(name)
        print("passed on", jax.devices()[0].device_kind, name, flush=True)
    rng = np.random.default_rng(3108)
    out = [dict(passed=ran)]

    def step(caches, kn, slots):
        return [write_kv_cache(kc, vc, kn, kn, slots) for kc, vc in caches]

    step = jax.jit(step, donate_argnums=(0,))
    for name, (prefill, decodes, t) in STEPS.items():
        caches = init_kv_cache(LAYERS, PAGES, PAGE, HKV, D, quantized=True)
        slots = jnp.asarray(step_slots(rng, PAGES, prefill, decodes, t))
        kn = jnp.asarray(rng.standard_normal((t, HKV, D)), jnp.bfloat16)
        compiled = step.lower(caches, kn, slots).compile()
        big = pool_sized_ops(compiled.as_text(), HKV * PAGES * PAGE * D)
        times = []
        for _ in range(args.reps + 3):
            t0 = time.perf_counter()
            caches = compiled(caches, kn, slots)
            jax.block_until_ready(caches)
            times.append((time.perf_counter() - t0) * 1e3)
        row = dict(step=name, write="int8", pool_sized_ops=big,
                   ms_median=statistics.median(times[3:]),
                   ms_min=min(times[3:]))
        print(row, flush=True)
        out.append(row)
        del caches
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--tp4", action="store_true")
    ap.add_argument("--int8", action="store_true")
    ap.add_argument("--aot", action="store_true")
    ap.add_argument("--tiny", action="store_true",
                    help="small pools: a rehearsal anywhere, never a speed")
    ap.add_argument("--reps", type=int, default=20)
    ap.add_argument("--out", default="chiprun_out/kv_write_shard_check.json")
    args = ap.parse_args()
    if args.tiny:
        global LAYERS, PAGES
        LAYERS, PAGES = 2, 320
    rows = []
    if args.tp4:
        rows += tp4(args)
    if args.int8:
        rows += int8(args)
    if not args.aot:
        dev = jax.devices()[0]
        print(f"device: {dev.platform} {dev.device_kind} "
              f"x{jax.device_count()}")
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        mode = "tp4" if args.tp4 else "int8"
        with open(args.out.replace(".json", f"_{mode}.json"), "w") as f:
            json.dump(dict(device=dev.device_kind, n=jax.device_count(),
                           rows=rows), f, indent=1)


if __name__ == "__main__":
    main()
