"""Time one layer's decode attention alone on the chip, at the shapes the
benchmark's cells run (PERF.md section 6, PR 33, holds the table).

    git archive 5cd880f | tar -x -C _chipdev/parent   # the tree before PR 33
    chiprun -- sh -c 'PYTHONPATH=_chipdev/parent python scripts/decode_attention_sweep.py --tree parent; python -m scripts.decode_attention_sweep --tree change'

The script reads only what both trees offer (``ops.paged_attention``,
``ops.ragged_paged_attention``, ``ops.paged_attention_ref``), so the same
file times either tree and ``--tree`` is a label.  Forms, per shape:

- ``entry``: ``paged_attention`` as ``forward_decode`` calls it.  Before
  PR 33 a kernel of its own that took one page an iteration; since, one
  token a segment through the ragged kernel's KV blocks.
- ``packed8``: the same rows as the decode rows of a MIXED step reach the
  ragged kernel: one token in each 8-row segment (before PR 33 every
  tile primed its own DMAs; since, the tile before it starts them).
- where the tree has them, the entry's tile parameters varied
  (``rows a cell x dma_slots``).

Each form is checked against ``paged_attention_ref`` on the same device,
then timed by the host's clock around ``block_until_ready`` (median of
``--reps`` calls) and by the device's own time under a profiler trace
(operations whose printed name holds ``attention``, and all operations,
per call).  A row also holds a digest of the output's bytes for each kv
head: ``--compare parent change`` reads two trees' files and says
whether every (shape, form) gave the same numbers head for head.
``--tiny`` runs a small version anywhere (interpret mode on the CPU): a
rehearsal of the script, never a speed.
"""

import argparse
import functools
import glob
import hashlib
import json
import os
import statistics
import tempfile
import time

import jax
import jax.numpy as jnp
import numpy as np

from vllm_omni_tpu import ops

PAGE = 16
#: None: the dispatch a served step gets; ``--tiny`` names the kernels
#: (interpret mode), or the rehearsal would time two references
USE_PALLAS = None

# name: rows of the bucket, rows that hold a sequence, kv heads, group,
# row width, value width (shared pool) or None, pool pages, table columns,
# contexts (lo, hi, log-normal median or None for uniform), int8
SHAPES = {
    "thinker_chat_64": (64, 63, 4, 8, 128, None, 8192, 528, (64, 1280, 330), False),
    "thinker_chat_16": (16, 16, 4, 8, 128, None, 8192, 528, (64, 1280, 330), False),
    "thinker_chat_8": (8, 7, 4, 8, 128, None, 8192, 528, (64, 1280, 330), False),
    "thinker_short_8": (8, 8, 4, 8, 128, None, 8192, 528, (80, 120, None), False),
    "thinker_doc_16": (16, 16, 4, 8, 128, None, 8192, 528, (4096, 8192, None), False),
    "thinker_chat_64_int8": (64, 63, 4, 8, 128, None, 8192, 528, (64, 1280, 330), True),
    "qwen3next_16": (16, 16, 2, 8, 256, None, 32768, 2080, (3000, 24000, 8192), False),
    "glm_24": (32, 24, 1, 20, 640, 512, 16384, 1104, (8192, 9400, None), False),
    # PR 49: the cells of 256 and 128 decode rows (LFM2's heads of 64 lie
    # two to a row of the pool: 4 rows of 128, a group of 8; granite has
    # 8 kv heads, its group of 4 padded to a sublane tile; Nemotron 2)
    "lfm2_256": (256, 255, 4, 8, 128, None, 16384, 400, (192, 6144, 800), False),
    "granite_128": (128, 127, 8, 4, 128, None, 16384, 400, (192, 6144, 800), False),
    "nemotron_128": (128, 127, 2, 16, 128, None, 16384, 400, (192, 6144, 800), False),
    "dots3_24": (32, 24, 1, 128, 640, 512, 16384, 1104, (8192, 9400, None), False),
}
TINY = {
    "tiny_two_pools": (4, 3, 2, 2, 128, None, 64, 8, (1, 100, None), False),
    "tiny_int8": (4, 3, 2, 2, 128, None, 64, 8, (1, 100, None), True),
    "tiny_shared": (4, 3, 1, 20, 256, 128, 64, 8, (1, 100, None), False),
}


def build(shape, seed, dtype):
    b, real, hkv, group, d, v_dim, pool, max_pages, (lo, hi, med), int8 = shape
    rs = np.random.default_rng(seed)
    if med:
        ctx = np.clip(rs.lognormal(np.log(med), 0.6, real), lo, hi)
    else:
        ctx = rs.uniform(lo, hi, real)
    lens = np.zeros(b, np.int32)
    lens[:real] = ctx.astype(np.int32)
    tables = np.zeros((b, max_pages), np.int32)
    perm = 1 + rs.permutation(pool - 1)  # scattered, page 0 unused
    used = 0
    for i in range(real):
        n = -(-int(lens[i]) // PAGE)
        tables[i, :n] = perm[used: used + n]
        used += n
    assert used <= perm.size, (used, perm.size)
    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(keys[0], (b, hkv * group, d), jnp.float32)

    def half(key):
        x = jax.random.normal(key, (hkv, pool, PAGE, d), jnp.float32)
        if not int8:
            return x.astype(dtype)
        scale = jnp.max(jnp.abs(x), axis=(2, 3)) / 127.0  # [Hkv, P]
        return (jnp.round(x / scale[..., None, None]).astype(jnp.int8),
                scale)

    k = half(keys[1])
    v = None if v_dim else half(keys[2])
    return (q.astype(dtype), k, v, jnp.asarray(tables), jnp.asarray(lens),
            v_dim, int(lens.max()))


def packed8(q, k, v, tables, lens, v_dim, **tiles):
    """One token in each 8-row segment, as a mixed step packs them."""
    b = q.shape[0]
    packed = jnp.zeros((b * 8,) + q.shape[1:], q.dtype).at[::8].set(q)
    out = ops.ragged_paged_attention(
        packed, k, v, tables, jnp.arange(b + 1, dtype=jnp.int32) * 8,
        (lens > 0).astype(jnp.int32), lens, b, v_dim=v_dim,
        use_pallas=USE_PALLAS, **tiles)
    return out[::8]


def entry(q, k, v, tables, lens, v_dim):
    return ops.paged_attention(q, k, v, tables, lens, v_dim=v_dim,
                               use_pallas=USE_PALLAS)


def forms_of(shape):
    out = {"entry": entry, "packed8": packed8}
    try:  # since PR 33: the entry's own tile parameters
        from vllm_omni_tpu.ops.ragged_paged_attention import _paged_attention
        from vllm_omni_tpu.ops.autotune import auto_ragged_blocks
    except ImportError:
        return out
    b, _, _, group, d, v_dim, *_, int8 = shape
    _, ppb, _ = auto_ragged_blocks(
        head_dim=d, page_size=PAGE, group=-(-group // 8) * 8,
        kv_itemsize=1 if int8 else 2, q_itemsize=2, quantized=int8,
        kv_pools=1 if v_dim else 2)
    for rows, slots in ((8, 2), (16, 3), (64, 2)):
        if rows > b and rows != 8:
            continue
        out[f"entry_{min(rows, b)}x{slots}"] = (
            lambda q, k, v, tables, lens, v_dim, rows=min(rows, b),
            slots=slots: _paged_attention(
                q, k, v, tables, lens, None, True, v_dim, rows, ppb, slots))
    out["packed8_3slots"] = (
        lambda *a: packed8(*a, dma_slots=3))
    return out


@functools.partial(jax.jit, static_argnames="v_dim")
def reference(q, k, v, tables, lens, v_dim):
    return ops.paged_attention_ref(q, k, v, tables, lens, None, v_dim)


@functools.partial(jax.jit, static_argnames=("fn", "v_dim"))
def jitted(*args, fn, v_dim):
    """One executable a (form, shape): the form is a static argument."""
    return fn(*args, v_dim)


def device_us(fn, args, calls: int) -> tuple[float, float]:
    """(us a call in operations named ``*attention*``, us a call in all
    device operations) from a profiler trace of ``calls`` calls."""
    from jax.profiler import ProfileData

    with tempfile.TemporaryDirectory() as tmp:
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(tmp, profiler_options=options)
        for _ in range(calls):
            out = fn(*args)
        jax.block_until_ready(out)
        jax.profiler.stop_trace()
        files = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"),
                          recursive=True)
        attn = total = 0.0
        for plane in ProfileData.from_file(files[0]).planes:
            if not plane.name.startswith("/device:TPU:"):
                continue
            for line in plane.lines:
                if line.name != "XLA Ops":
                    continue
                for ev in line.events:
                    total += ev.duration_ns
                    if "attention" in ev.name.partition(" = ")[0]:
                        attn += ev.duration_ns
    return attn / calls / 1e3, total / calls / 1e3


def _file(tree: str, tiny: bool) -> str:
    return os.path.join(
        "chiprun_out",
        f"decode_attention_sweep_{tree}{'_tiny' if tiny else ''}.json")


def compare(a: str, b: str, tiny: bool = False) -> int:
    """A line a (shape, form) both trees ran: kernel time and whether the
    outputs' digests agree for every kv head; 1 if any pair differs."""
    rows = {t: {(r["shape"], r["form"]): r
                for r in json.load(open(_file(t, tiny)))["rows"]}
            for t in (a, b)}
    differ = 0
    for key in rows[a]:
        ra, rb = rows[a][key], rows[b].get(key)
        if rb is None or "digests" not in ra or "digests" not in rb:
            continue
        same = ra["digests"] == rb["digests"]
        differ += not same
        ta, tb = (r.get("kernel_us") or r["host_ms"] * 1e3 for r in (ra, rb))
        print(f"{key[0]:<22} {key[1]:<16} {a} {ta:9.1f} us  {b} {tb:9.1f} us"
              f"  {100 * (tb / ta - 1):+6.1f}%  "
              + ("equal head for head" if same else "DIFFER"))
    return 1 if differ else 0


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--tree", default="change")
    ap.add_argument("--reps", type=int, default=20)
    ap.add_argument("--seed", type=int, default=33)
    ap.add_argument("--only", default="")
    ap.add_argument("--forms", default="",
                    help="comma-separated forms; all where empty")
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--compare", nargs=2, metavar="TREE",
                    help="read two trees' files; no chip")
    ns = ap.parse_args()
    if ns.compare:
        raise SystemExit(compare(*ns.compare, tiny=ns.tiny))
    global USE_PALLAS
    USE_PALLAS = True if ns.tiny else None
    dev = jax.devices()[0]
    shapes, dtype, tol = (TINY, jnp.float32, 1e-4) if ns.tiny else (
        SHAPES, jnp.bfloat16, 4e-2)
    if not ns.tiny:
        assert dev.platform == "tpu", "times come from the chip only"
    rows = []
    for name, shape in shapes.items():
        if ns.only and not any(o in name for o in ns.only.split(",")):
            continue
        *args, v_dim, longest = build(shape, ns.seed, dtype)
        q, k, v, tables, lens = args
        cols = -(-longest // PAGE)  # the gather reference takes every column
        want = np.asarray(reference(q, k, v, tables[:, :cols], lens, v_dim),
                          np.float32)
        for form, fn in forms_of(shape).items():
            if ns.forms and form not in ns.forms.split(","):
                continue
            run = functools.partial(jitted, fn=fn, v_dim=v_dim)
            row = {"tree": ns.tree, "shape": name, "form": form}
            try:
                got = np.asarray(jax.block_until_ready(run(*args)),
                                 np.float32)
                times = []
                for _ in range(ns.reps):
                    t0 = time.perf_counter()
                    jax.block_until_ready(run(*args))
                    times.append((time.perf_counter() - t0) * 1e3)
                row["host_ms"] = statistics.median(times)
                if dev.platform == "tpu":
                    row["kernel_us"], row["device_us"] = device_us(
                        run, args, 10)
                # a row of context 0 reads zeros from the kernels and a
                # mean of page 0 from the gather reference: not compared
                real = np.asarray(lens) > 0
                row["max_err"] = float(np.abs(got - want)[real].max())
                # the output's bytes, kv head by kv head: two trees whose
                # digests agree gave the same numbers head for head
                hkv = shape[2]
                row["digests"] = [
                    hashlib.sha256(np.ascontiguousarray(
                        got.reshape(got.shape[0], hkv, -1)[:, h])
                        .tobytes()).hexdigest()[:16] for h in range(hkv)]
                row["ok"] = bool(row["max_err"] <= tol)
            except Exception as e:  # a refused form is a finding
                row["refused"] = str(e).splitlines()[0][:200]
            rows.append(row)
            print(json.dumps(row), flush=True)
    os.makedirs("chiprun_out", exist_ok=True)
    with open(_file(ns.tree, ns.tiny), "w") as f:
        json.dump({"device": dev.device_kind, "tree": ns.tree,
                   "rows": rows}, f, indent=1)
    raise SystemExit(1 if any(not r.get("ok", True) for r in rows) else 0)


if __name__ == "__main__":
    main()
