"""Time the ragged paged-attention kernel alone on the chip, at the
shapes of the benchmark's cells, over candidate (q_block,
pages_per_block, dma_slots) triples (ops/autotune.py
``auto_ragged_blocks`` is checked against this table; PERF.md holds it).

    chiprun -- python -m scripts.ragged_kernel_sweep

Each case is one packed step (the thinker's geometry: Hkv 4, group 8,
D 128, page 16, a pool of 8192 pages, 64 metadata rows over 528 table
columns, bf16; ``--geo`` names another cell's: PR 49): decode rows
first, then one prefill chunk, packed as ``ARModelRunner`` packs them.
Every candidate is checked against a plain per-sequence attention
computed in float32 on the same device, then timed (median of
``--reps`` calls, each ended by ``block_until_ready``).  A candidate the
compiler refuses is reported, not fatal.  ``--tiny`` runs a small
version anywhere (the CPU takes the kernel in interpret mode): a
rehearsal of the script, never a speed.  A row also holds a digest of
the output's bytes for each kv head; ``--compare A.json B.json`` says
whether two trees' files agree head for head (no chip).
"""

import argparse
import hashlib
import importlib
import json
import os
import statistics
import time

import jax
import jax.numpy as jnp
import numpy as np

rpa = importlib.import_module("vllm_omni_tpu.ops.ragged_paged_attention")
from vllm_omni_tpu.ops.autotune import auto_ragged_blocks  # noqa: E402

TB = rpa.DEFAULT_TOKEN_BLOCK

# (name, packed bucket T, decode rows, decode context, chunk tokens,
#  chunk context including the chunk)
CASES = [
    ("doc_ctx4096", 4096, 15, 4100, 2048, 4096),
    ("doc_ctx8192", 4096, 15, 4100, 2048, 8192),
    ("chat_p1024", 2048, 63, 400, 1024, 1024),
    ("chat_p256", 1024, 63, 400, 256, 256),
]
TINY_CASES = [("tiny", 128, 3, 40, 64, 96)]

# kv heads, group, row width, value width of a shared pool or None
GEOS = {
    "thinker": (4, 8, 128, None),    # LFM2's paired heads of 64 too
    "qwen3next": (2, 8, 256, None),
    "granite": (8, 4, 128, None),
    "nemotron": (2, 16, 128, None),
    "glm": (1, 20, 640, 512),        # one latent kv head: the control
}

# (q_block, pages_per_block, dma_slots); the first row is the tile the
# kernel had before PR 26 (one 8-token block, one page an iteration)
CANDIDATES = [
    (8, 1, 4), (8, 16, 2), (16, 16, 2), (32, 8, 2), (32, 16, 2),
    (32, 16, 3), (32, 32, 2), (64, 16, 2), (64, 16, 3), (64, 32, 2),
    (64, 32, 3), (64, 64, 2), (128, 16, 2), (128, 32, 2),
]


def build(case, hkv, group, d, page, pool, s_max, max_pages, dtype, seed,
          v_dim=None):
    """One packed step: (args of _ragged_attention, per-sequence specs)."""
    _, t, n_dec, dec_ctx, chunk, chunk_ctx = case
    rng = np.random.default_rng(seed)
    specs = [(dec_ctx + 7 * i, 1) for i in range(n_dec)]
    specs.append((chunk_ctx, chunk))
    cu = np.zeros(s_max + 1, np.int32)
    q_lens = np.zeros(s_max, np.int32)
    seq_lens = np.zeros(s_max, np.int32)
    tables = np.zeros((s_max, max_pages), np.int32)
    perm = rng.permutation(np.arange(1, pool))  # scattered, page 0 unused
    total = used = 0
    for i, (ctx, qn) in enumerate(specs):
        cu[i], q_lens[i], seq_lens[i] = total, qn, ctx
        total += rpa.align_to_block(qn, TB)
        pn = -(-ctx // page)
        tables[i, :pn] = perm[used: used + pn]
        used += pn
    assert total <= t and used <= perm.size, (total, t, used)
    cu[len(specs):] = total
    key = jax.random.PRNGKey(seed)
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (t, hkv * group, d), jnp.float32).astype(dtype)
    shape = (hkv, pool, page, d)
    k_cache = jax.random.normal(kk, shape, jnp.float32).astype(dtype)
    v_cache = None if v_dim else jax.random.normal(
        kv, shape, jnp.float32).astype(dtype)
    args = (q, k_cache, v_cache, jnp.asarray(tables), jnp.asarray(cu),
            jnp.asarray(q_lens), jnp.asarray(seq_lens),
            jnp.asarray([len(specs)], jnp.int32))
    return args, specs, cu


@jax.jit
def _dense_seq(q, k, v, ctx_len):
    """Plain causal attention of one sequence's chunk in float32:
    q [qn, Hkv, G, D] sits at the LAST qn positions of k/v [Hkv, C, D]."""
    qn, c = q.shape[0], k.shape[1]
    s = jnp.einsum("thgd,hcd->hgtc", q.astype(jnp.float32),
                   k.astype(jnp.float32)) / np.sqrt(q.shape[-1])
    q_pos = ctx_len - qn + jnp.arange(qn)
    mask = jnp.arange(c)[None, :] <= q_pos[:, None]
    p = jax.nn.softmax(jnp.where(mask, s, -1e30), axis=-1)
    return jnp.einsum("hgtc,hcd->thgd", p, v.astype(jnp.float32))


def reference(args, specs, cu, page, v_dim=None):
    q, k_cache, v_cache, tables = args[:4]
    t, h, d = q.shape
    hkv = k_cache.shape[0]
    dv = v_dim or d
    out = np.zeros((t, h, dv), np.float32)
    for i, (ctx, qn) in enumerate(specs):
        pn = -(-ctx // page)
        ids = tables[i, :pn]
        k = k_cache[:, ids].reshape(hkv, pn * page, d)[:, :ctx]
        v = k[..., :dv] if v_cache is None else (
            v_cache[:, ids].reshape(hkv, pn * page, d)[:, :ctx])
        lo = int(cu[i])
        qi = q[lo: lo + qn].reshape(qn, hkv, h // hkv, d)
        out[lo: lo + qn] = np.asarray(
            _dense_seq(qi, k, v, ctx)).reshape(qn, h, dv)
    return out


def compare(a: str, b: str) -> int:
    """A line a (case, tile) both files hold; 1 if any output differs."""
    rows = [{(r["case"], r["q_block"], r["pages_per_block"],
              r["dma_slots"]): r for r in json.load(open(f))["rows"]}
            for f in (a, b)]
    differ = 0
    for key, ra in rows[0].items():
        rb = rows[1].get(key)
        if rb is None or "digests" not in ra or "digests" not in rb:
            continue
        same = ra["digests"] == rb["digests"]
        differ += not same
        print(f"{ra.get('geo', ''):<10} {key[0]:<12} {key[1:]} "
              f"{ra['ms']:8.3f} ms  {rb['ms']:8.3f} ms  "
              f"{100 * (rb['ms'] / ra['ms'] - 1):+6.1f}%  "
              + ("equal head for head" if same else "DIFFER"))
    return 1 if differ else 0


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--reps", type=int, default=20)
    ap.add_argument("--seed", type=int, default=26)
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--auto-only", action="store_true",
                    help="time the picker's tile alone: the control of a "
                         "PR that changes the kernel, parent beside change")
    ap.add_argument("--out", default="chiprun_out/ragged_sweep.json")
    ap.add_argument("--geo", default="thinker", choices=sorted(GEOS))
    ap.add_argument("--compare", nargs=2, metavar="JSON",
                    help="two trees' --out files: times, and whether the "
                         "outputs agree head for head; no chip")
    ns = ap.parse_args()
    if ns.compare:
        raise SystemExit(compare(*ns.compare))
    dev = jax.devices()[0]
    print(f"device: {dev.platform} {dev.device_kind} x{jax.device_count()}")
    if ns.tiny:
        geo = dict(hkv=2, group=2, d=128, page=8, pool=64, s_max=8,
                   max_pages=16, dtype=jnp.float32)
        cases, cands, tol = TINY_CASES, [(8, 1, 2), (16, 2, 2), (32, 4, 3)], 1e-4
    else:
        assert dev.platform == "tpu", "times come from the chip only"
        hkv, group, d, v_dim = GEOS[ns.geo]
        geo = dict(hkv=hkv, group=group, d=d, page=16, pool=8192, s_max=64,
                   max_pages=528, dtype=jnp.bfloat16, v_dim=v_dim)
        cases, cands, tol = CASES, CANDIDATES, 4e-2
    v_dim = geo.get("v_dim")
    auto = auto_ragged_blocks(
        head_dim=geo["d"], page_size=geo["page"], group=geo["group"],
        kv_itemsize=jnp.dtype(geo["dtype"]).itemsize,
        q_itemsize=jnp.dtype(geo["dtype"]).itemsize,
        kv_pools=1 if v_dim else 2)
    print(f"auto_ragged_blocks -> {auto}")
    if ns.auto_only:
        cands = [auto]
    elif auto not in cands:
        cands = cands + [auto]
    rows = []
    for case in cases:
        args, specs, cu = build(case, seed=ns.seed, **geo)
        want = reference(args, specs, cu, geo["page"], v_dim)
        for bq, ppb, slots in cands:
            def run(a=args, bq=bq, ppb=ppb, slots=slots):
                return rpa._ragged_attention(
                    *a, None, TB, True, bq, ppb, slots, v_dim)
            row = {"geo": ns.geo, "case": case[0], "q_block": bq,
                   "pages_per_block": ppb,
                   "dma_slots": slots, "auto": (bq, ppb, slots) == auto}
            try:
                got = np.asarray(jax.block_until_ready(run()), np.float32)
                times = []
                for _ in range(ns.reps):
                    t0 = time.perf_counter()
                    jax.block_until_ready(run())
                    times.append((time.perf_counter() - t0) * 1e3)
                row["ms"] = statistics.median(times)
                row["max_err"] = float(np.abs(got - want).max())
                row["ok"] = bool(row["max_err"] <= tol)
                row["digests"] = [
                    hashlib.sha256(np.ascontiguousarray(
                        got.reshape(got.shape[0], geo["hkv"], -1)[:, h])
                        .tobytes()).hexdigest()[:16]
                    for h in range(geo["hkv"])]
            except Exception as e:  # a refused candidate is a finding
                row["refused"] = str(e).splitlines()[0][:200]
            rows.append(row)
            print(json.dumps(row), flush=True)
    os.makedirs(os.path.dirname(ns.out) or ".", exist_ok=True)
    with open(ns.out, "w") as f:
        json.dump({"device": dev.device_kind, "platform": dev.platform,
                   "auto": auto, "rows": rows}, f, indent=1)
    print(f"\n{'case':<12} {'bq':>3} {'ppb':>3} {'slots':>5} "
          f"{'ms':>9} {'max_err':>9}")
    for r in rows:
        tail = (f"{r['ms']:9.3f} {r['max_err']:9.2e}"
                + ("" if r["ok"] else "  WRONG") if "ms" in r
                else f"  refused: {r['refused']}")
        print(f"{r['case']:<12} {r['q_block']:>3} {r['pages_per_block']:>3} "
              f"{r['dma_slots']:>5} {tail}" + ("  <- auto" if r["auto"] else ""))
    bad = [r for r in rows if "ms" in r and not r["ok"]]
    raise SystemExit(1 if bad else 0)


if __name__ == "__main__":
    main()
