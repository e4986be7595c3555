"""Times the chunked gated delta rule alone (``ops/gated_delta_rule.py
gated_delta_rule_ragged``) at Qwen3-Next's widths: the ``jax.numpy`` form
(triangular solve, scan, ``einsum``s) and the kernel over its head block,
on the steps ``qwen3next_longdoc_sat`` runs.

    chiprun -- python3 -m scripts.gdn_kernel_sweep [--tiny]

Shapes: ``mixed`` (T 2048, S 32: a prompt's chunk of 1 980 tokens beside
seven one-token rows), ``decode`` (T 256, 16 one-token rows), ``mixed4k``
(T 4096: 96 chunk slots where ``mixed`` has 64, the same tokens in them,
which is what S10 asked: does the solve cost by slots or by work).  Prints
one JSON line a shape: milliseconds a call by the host's clock (``--iters``
calls that end in ``block_until_ready``) for every form, the device's
operations of one call by name from a profiler trace (the five longest),
the largest difference between kernel and ``jax.numpy`` form and of each
from the token-by-token scan.  ``--tiny`` rehearses it on the CPU
(interpret mode, no trace).
"""

from __future__ import annotations

import argparse
import collections
import functools
import glob
import json
import os
import sys
import tempfile
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))

SHAPES = {  # name -> (T, S, tokens of each live sequence)
    "mixed": (2048, 32, [1980] + [1] * 7),
    "decode": (256, 32, [1] * 16),
    "mixed4k": (4096, 32, [1980] + [1] * 7),
}
TINY = {"mixed": (256, 4, [150, 1, 1]), "decode": (64, 4, [1] * 3)}


def device_ops(fn, args, calls: int = 3) -> dict[str, float]:
    """Milliseconds a call of every device operation, by its name."""
    import jax
    from jax.profiler import ProfileData

    ops: collections.Counter = collections.Counter()
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
        for plane in ProfileData.from_file(files[0]).planes:
            if not plane.name.startswith("/device:TPU:"):
                continue
            for line in plane.lines:
                if line.name == "XLA Ops":
                    for ev in line.events:
                        ops[ev.name.partition(" = ")[0].lstrip("%")] += \
                            ev.duration_ns / calls / 1e6
    return dict(ops)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--blocks", type=int, nargs="*", default=[8, 16, 32])
    ap.add_argument("--shapes", nargs="*", default=None)
    ap.add_argument("--tiny", action="store_true")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    from vllm_omni_tpu.ops import gated_delta_rule as gdr

    hv, d = (2, 128) if args.tiny else (32, 128)
    shapes = TINY if args.tiny else SHAPES
    on_chip = jax.devices()[0].platform == "tpu"
    forms = {"jax_numpy": jax.jit(functools.partial(
        gdr.gated_delta_rule_ragged, use_pallas=False))}
    # the kernel's entry is a ``jax.jit`` of its own, the head block static;
    # a block over the heads there are takes them all
    forms.update({f"kernel_hb{hb}": functools.partial(
        gdr._chunked_kernel, head_block=max(hb, 8))
        for hb in sorted({min(hb, hv) for hb in args.blocks})})
    # the scan over the longest sequence and a one-token one
    scan = jax.jit(gdr.gated_delta_rule_scan)
    for name in args.shapes or shapes:
        t, s, lens = shapes[name]
        cu = [0]
        for n in lens:
            cu.append(cu[-1] + -(-n // 8) * 8)
        ks = jax.random.split(jax.random.PRNGKey(len(lens)), 6)
        q = gdr.l2_normalize(jax.random.normal(ks[0], (t, hv, d))) * d ** -0.5
        k = gdr.l2_normalize(jax.random.normal(ks[1], (t, hv, d)))
        v = jax.random.normal(ks[2], (t, hv, d))
        g = -jax.nn.softplus(jax.random.normal(ks[3], (t, hv)))
        beta = jax.nn.sigmoid(jax.random.normal(ks[4], (t, hv)))
        state = jax.random.normal(ks[5], (s, hv, d, d))
        cu_q = jnp.asarray(cu + [cu[-1]] * (s + 1 - len(cu)), jnp.int32)
        q_lens = jnp.asarray(lens + [0] * (s - len(lens)), jnp.int32)
        ops = (q, k, v, g, beta, state, cu_q, q_lens)

        want = []
        for i in {0, len(lens) - 1}:
            rows = slice(cu[i], cu[i] + lens[i])
            want.append((i, rows) + scan(
                q[rows], k[rows], v[rows], g[rows], beta[rows], state[i]))
        line = {"shape": name, "t": t, "s": s, "lens": lens,
                "device": jax.devices()[0].device_kind, "ms_a_call": {},
                "from_scan": {}, "device_ops_ms": {}}
        got = {}
        for form, fn in forms.items():
            o, new = got[form] = jax.block_until_ready(fn(*ops))
            t0 = time.perf_counter()
            for _ in range(args.iters):
                out = fn(*ops)
            jax.block_until_ready(out)
            line["ms_a_call"][form] = \
                (time.perf_counter() - t0) * 1e3 / args.iters
            line["from_scan"][form] = max(
                max(float(jnp.abs(o[rows] - want_o).max()),
                    float(jnp.abs(new[i] - want_s).max()))
                for i, rows, want_o, want_s in want)
            if on_chip:
                top = sorted(device_ops(fn, ops).items(),
                             key=lambda kv: -kv[1])
                line["device_ops_ms"][form] = {
                    "all": sum(ms for _, ms in top),
                    **{op: round(ms, 4) for op, ms in top[:5]}}
        real = jnp.zeros((t,), bool)
        for start, n in zip(cu, lens):
            real = real.at[start:start + n].set(True)
        base_o, base_s = got["jax_numpy"]
        line["from_jax_numpy"] = {
            form: max(float(jnp.abs(
                jnp.where(real[:, None, None], o - base_o, 0.0)).max()),
                float(jnp.abs(new - base_s).max()))
            for form, (o, new) in got.items() if form != "jax_numpy"}
        print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main()
