"""An expert share's rows (``ops/moe.py routed_moe_share``): what the rows of
ABSENT experts cost, what cutting them would change, and the forms of the cut
that were tried (PR 53; ROADMAP S12, D20).

A share routes all T x k pairs, and three in four or seven in eight of them
belong to experts that live elsewhere.  The held pairs sort in front, so a
share could work on the first ``share_cap`` sorted rows (the pairs it expects
times ``SHARE_SLACK``) and on the rows behind them only in a step that holds
more.  Alone that wins a quarter to two fifths of the share's time
(``FORMS``, timed below); in a step program it needs a ``while`` or a
``lax.cond`` to stay exact, and with either around the share's rows the
2 048-token step of dots3-note-prev never returns on the v5e (libtpu 0.0.34),
so the tree serves none of them.  For the five configurations under
``benchmark/configs/`` that hold a share of their router's experts:

    python3 -m scripts.moe_share_rows_sweep --plan
    chiprun -- python3 -m scripts.moe_share_rows_sweep [--only qwen3next]
    chiprun -- python3 -m scripts.moe_share_rows_sweep --served --only ..
    chiprun -- python3 -m scripts.moe_share_rows_sweep --probe cond \
        --workload dots3note_agent_sat --seed 1 --seconds 45 --trace 0

- ``--plan`` (no chip: shapes alone): for every decode and token bucket, the
  pairs, ``share_cap`` and ``grouped_matmul.choice()`` of both products as
  served (all T x k rows over the router's width) and under the cut (``cap``
  rows over the held experts; then the rows behind them).  Fewer resident
  rows fit the kernel's VMEM budget at more buckets: the lines marked ``*``
  are the step programs whose form would change.
- no option (the chip): the whole share alone (routing, sort, gather, grouped
  MLP, scatter-add) at each configuration's 2 048-token shape in every form
  of ``FORMS``, with the router steered so that the held share of the picks
  is 0.75, 1, 1.33 and 2 times the mean (the last holds more than ``cap``).
  Milliseconds a call by the host's clock around bursts of calls that end in
  ``block_until_ready``, and each form's largest difference from ``parent``.
- ``--served``: a short served run of one configuration (its seeded
  weights, prompts of the cell's bytes in 2 048-token chunks, a few decoded
  tokens, no warm-up), the held pairs of every share call counted as the
  step programs run (a host callback on ``_grouped_mlp``, set HERE and no
  option of the program): by pairs of a step, the largest and the mean held
  share against ``cap``, and how many calls held more than ``cap``.
- ``--probe FORM`` runs ``scripts.warmup_probe`` (its arguments and
  ``PROBE_*`` variables follow) with that form in ``routed_moe_share``'s
  place: how a form is tried in a step program, 2-4 chip-minutes.

Prints a JSON line a case; the tables go to ``chiprun_out/``.  ``--tiny``
rehearses the timing on the CPU, ``--tiny-config FILE`` the served run.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))

#: held shares of a step's picks, as multiples of the mean (E_held / width)
SHARES = (0.75, 1.0, 1.33, 2.0)
TINY = {"name": "tiny", "tokens": 64, "k": 4, "width": 16, "held": 4,
        "hidden": 32, "router_in": 32, "up": 48, "inter": 24}
#: rows a cut share would always work on, over the pairs it expects.  In a
#: layer and 2 048-token chunk of the five configurations' served runs the
#: held share of the pairs read at most 1.06-1.29 times its mean in four,
#: and 1.84 times in dots3's, where 11 of 64 calls held more than 1.5 times
#: (PERF.md section 5, PR 53): no one slack fits, so a cut has to be exact
SHARE_SLACK = 1.5


def share_cap(pairs: int, e_held: int, width: int) -> int:
    """Sorted rows a cut share that holds ``e_held`` of a router's ``width``
    experts would always work on, of the ``pairs`` picks it routes: the
    pairs it expects times ``SHARE_SLACK``, up to a whole sublane tile,
    never more than ``pairs``."""
    from vllm_omni_tpu.ops.grouped_matmul import SUBLANES

    want = math.ceil(pairs * e_held * SHARE_SLACK / width)
    return min(-(-want // SUBLANES) * SUBLANES, pairs)


def share_form(form: str):
    """``routed_moe_share``'s signature in one of the forms PR 53 tried:
    ``parent`` (until PR 53: two gathers of T x k scalars by the sort's
    order), ``tree`` (what is served), and the cuts: ``windows`` (a
    ``fori_loop`` over ``ceil(n_held / cap)`` windows of ``cap`` rows),
    ``first_then_windows`` (the first window outside the loop),
    ``static_windows`` (every window, skipped under ``lax.cond`` past
    ``n_held``), ``one_window`` (a loop whose one window is all T x k
    rows: no cut), ``cond`` (the first ``cap`` rows, then all the rest
    under one ``lax.cond``), ``first`` (the first ``cap`` rows alone: NOT
    exact, the control that has no control flow).  Every cut but ``first``
    stopped dots3's 2 048-token step; ``first`` and ``tree`` returned."""
    import jax
    import jax.numpy as jnp

    from vllm_omni_tpu.ops import moe

    if form == "tree":
        return moe.routed_moe_share

    def share(x, router_w, gate_up, down, k, expert_offset, renormalize=True,
              float32_logits=True, act=None, route_on=None, **router):
        e_held, pairs = gate_up.shape[0], x.shape[0] * k
        cap = (pairs if form in ("parent", "one_window")
               else share_cap(pairs, e_held, router_w.shape[-1]))
        topk_idx, topk_w = moe.router_topk(
            x if route_on is None else route_on, router_w, k, renormalize,
            **{"float32_logits": float32_logits, **router})
        flat_e = topk_idx.reshape(-1) - expert_offset
        mine = (flat_e >= 0) & (flat_e < e_held)
        local_e = jnp.where(mine, flat_e, e_held)
        order = jnp.argsort(local_e)
        w = topk_w.reshape(-1)[order]
        sizes = jnp.bincount(local_e, length=e_held + 1)[:e_held]
        if form == "parent":
            y = moe._grouped_mlp(x[order // k], gate_up, down, sizes, act,
                                 router_w.shape[-1])
            y = jnp.where(mine[order][:, None],
                          y * w[:, None].astype(y.dtype), 0)
            return jnp.zeros_like(x).at[order // k].add(y)
        ends = jnp.cumsum(sizes)
        starts, n_held = ends - sizes, ends[-1]
        windows = -(-pairs // cap)
        order = jnp.pad(order, (0, windows * cap - pairs))
        w = jnp.pad(w, (0, windows * cap - pairs))

        def add_rows(out, lo, rows):
            """``out`` with the sorted rows ``lo .. lo + rows`` added."""
            token_of = jax.lax.dynamic_slice(order, (lo,), (rows,)) // k
            group_sizes = (jnp.clip(ends, lo, lo + rows)
                           - jnp.clip(starts, lo, lo + rows))
            y = moe._grouped_mlp(x[token_of], gate_up, down, group_sizes,
                                 act)
            y = y * jax.lax.dynamic_slice(w, (lo,), (rows,))[:, None].astype(
                y.dtype)
            live = lo + jnp.arange(rows) < n_held
            return out.at[token_of].add(jnp.where(live[:, None], y, 0))

        def window(i, out):
            return add_rows(out, i * cap, cap)

        zeros, n = jnp.zeros_like(x), -(-n_held // cap)
        if form in ("windows", "one_window"):
            return jax.lax.fori_loop(0, n, window, zeros)
        if form == "first_then_windows":
            return jax.lax.fori_loop(1, n, window, window(0, zeros))
        if form == "static_windows":
            return jax.lax.fori_loop(0, windows, lambda i, out: jax.lax.cond(
                i * cap < n_held, window, lambda i, out: out, i, out), zeros)
        out = window(0, zeros)
        if form == "cond" and cap < pairs:
            out = jax.lax.cond(
                n_held > cap, lambda o: add_rows(o, cap, pairs - cap),
                lambda o: o, out)
        elif form not in ("cond", "first"):
            raise SystemExit(f"no form {form!r}")
        return out
    return share


def uncapped_share(x, rw, gu, dn, k, offset, act=None, route_on=None,
                   **router):
    """``routed_moe_share`` as it was until PR 53, which is its definition:
    every one of the T * k pairs gathered, multiplied and scatter-added,
    the values of the absent experts' pairs masked to zero."""
    return share_form("parent")(x, rw, gu, dn, k, offset, act=act,
                                route_on=route_on, **router)


def share_configs(only: str = "") -> list[dict]:
    """The one-chip configurations that hold a share of their experts."""
    from scripts.moe_grouped_sweep import cell_calls

    picked = [s for s in only.split(",") if s]
    found = []
    for path in sorted((ROOT / "benchmark" / "configs").glob("*.json")):
        config = json.loads(path.read_text())
        if config.get("tensor_parallel_size", 1) != 1:
            continue
        _, _, width, e, _, _ = next(iter(cell_calls(config).values()))
        if e != width and (not picked
                           or any(s in config["name"] for s in picked)):
            found.append(config)
    return found


def plan(config: dict) -> list[dict]:
    """A line a bucket of the configuration: pairs, cap, both products'
    form as served and under the cut (``cap`` rows over the held experts;
    the rows behind them), and the VMEM the kernel would ask for."""
    from scripts.moe_grouped_sweep import cell_calls
    from vllm_omni_tpu.ops import grouped_matmul as gm

    calls = cell_calls(config)
    lines = []
    for t in sorted({call[0] for call in calls.values()}):
        line = {"config": config["name"], "rows": t}
        for product in ("up", "down"):
            _, p, width, held, k, n = calls[f"{t}_{product}"]
            cap = share_cap(p, held, width)

            def form(rows: int, over: int) -> tuple[str, float]:
                """(form, VMEM MB the kernel would ask) of ``rows`` rows."""
                if not rows:
                    return "-", 0.0
                # off the kernel's tiling (a tiny configuration) none takes it
                took = ("ragged_dot" if gm.kernel_reason(k, n)
                        else gm.choice(rows, over, k, n))
                return took, round(
                    gm.tiles(rows, over, k, n, 2)[2] / 2 ** 20, 1)

            after, rest = form(cap, held), form(p - cap, held)
            line.update({
                "pairs": p, "cap": cap,
                f"{product}_before": form(p, width)[0],
                f"{product}_after": after[0],
                f"{product}_vmem_mb": after[1],
                # the rows behind cap, in the branch of a step that needs them
                f"{product}_rest": rest[0],
                f"{product}_rest_vmem_mb": rest[1]})
        line["changed"] = any(line[f"{p}_before"] != line[f"{p}_after"]
                              for p in ("up", "down"))
        lines.append(line)
    return lines


def plan_table(lines: list[dict]) -> str:
    head = ("| configuration | step rows | pairs | cap | up: before -> after "
            "(VMEM MB); the rest | down: before -> after (VMEM MB); the rest "
            "| |\n| --- | --- | --- | --- | --- | --- | --- |\n")
    return head + "\n".join(
        f"| `{ln['config']}` | {ln['rows']} | {ln['pairs']} | {ln['cap']} | "
        + " | ".join(f"{ln[p + '_before']} -> {ln[p + '_after']} "
                     f"({ln[p + '_vmem_mb']}); {ln[p + '_rest']} "
                     f"({ln[p + '_rest_vmem_mb']})" for p in ("up", "down"))
        + f" | {'*' if ln['changed'] else ''} |" for ln in lines)


def dims_of(config: dict, tokens: int = 2048) -> dict:
    """The share's shapes at a ``tokens``-row step of a configuration."""
    from benchmark import spec
    from scripts.moe_grouped_sweep import cell_calls

    calls = cell_calls(config)
    _, p, width, held, hidden, up = calls[f"{tokens}_up"]
    cfg = spec.arch_of(config).program_config(config)
    return {"name": config["name"], "tokens": tokens, "k": p // tokens,
            "width": width, "held": held, "hidden": hidden,
            "router_in": cfg.hidden_size, "up": up,
            "inter": calls[f"{tokens}_down"][4]}


def steered(d: dict, share: float, seed: int, dtype):
    """(x, router_w, up, down, route_on, held share of the picks): a
    softmax router whose held experts (the first ``held``) get the lift on
    every token (the router's first input is 1) that brings their share of
    the T x k picks nearest ``share``, found by bisection on the host."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    x = jax.random.normal(ks[0], (d["tokens"], d["hidden"]), jnp.float32)
    latent = d["router_in"] != d["hidden"]
    on = (jax.random.normal(ks[1], (d["tokens"], d["router_in"]),
                            jnp.float32) if latent else x).at[:, 0].set(1.0)
    rw = jax.random.normal(ks[2], (d["router_in"], d["width"]),
                           jnp.float32) * d["router_in"] ** -0.5
    rw = rw.at[0].set(0.0)
    logits = np.asarray(on @ rw)

    def held_share(lift: float) -> float:
        steered = logits.copy()
        steered[:, :d["held"]] += lift
        top = np.argpartition(-steered, d["k"] - 1, axis=1)[:, :d["k"]]
        return float((top < d["held"]).mean())

    lo, hi = -30.0, 30.0
    for _ in range(40):
        mid = (lo + hi) / 2
        lo, hi = (mid, hi) if held_share(mid) < share else (lo, mid)
    rw = rw.at[0, :d["held"]].set(hi)
    up = (jax.random.uniform(ks[3], (d["held"], d["hidden"], d["up"]),
                             jnp.float32, -1, 1)
          * d["hidden"] ** -0.5).astype(dtype)
    down = (jax.random.uniform(ks[4], (d["held"], d["inter"], d["hidden"]),
                               jnp.float32, -1, 1)
            * d["inter"] ** -0.5).astype(dtype)
    cast = (lambda a: a.astype(dtype))
    return (cast(x if latent else on), cast(rw), up, down,
            cast(on) if latent else None, held_share(hi))


def time_forms(d: dict, forms: list[str], reps: int, dtype) -> list[dict]:
    """Every form of ``forms`` at each of ``SHARES``."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from vllm_omni_tpu.ops.activation import relu2

    act = None if d["up"] == 2 * d["inter"] else relu2
    mean = d["held"] / d["width"]
    pairs = d["tokens"] * d["k"]
    cap = share_cap(pairs, d["held"], d["width"])
    # a form compiles once: the four routers differ in values alone
    runs = {form: jax.jit(lambda x, rw, up, down, on, fn=share_form(form): fn(
        x, rw, up, down, d["k"], 0, act=act, route_on=on)) for form in forms}
    out = []
    for i, times in enumerate(SHARES):
        x, rw, up, down, on, got = steered(d, min(times * mean, 1.0),
                                           53 + i, dtype)
        row = {"config": d["name"], "tokens": d["tokens"], "pairs": pairs,
               "cap": cap, "held_share_x_mean": round(got / mean, 3),
               "held_pairs": round(got * pairs)}
        results = {}
        for form, run in runs.items():
            results[form] = jax.block_until_ready(run(x, rw, up, down, on))
            bursts = []
            for _ in range(3):
                t0 = time.perf_counter()
                for _ in range(reps):
                    y = run(x, rw, up, down, on)
                jax.block_until_ready(y)
                bursts.append((time.perf_counter() - t0) * 1e3 / reps)
            row[f"{form}_ms"] = round(min(bursts), 4)
        want = results[forms[0]].astype(jnp.float32)
        row["max_abs_diff"] = {form: float(jnp.abs(
            want - got.astype(jnp.float32)).max())
            for form, got in results.items() if form != forms[0]}
        row["result_absmax"] = float(jnp.abs(want).max())
        row["finite"] = all(bool(np.isfinite(np.asarray(
            got, np.float32)).all()) for got in results.values())
        row["device"] = jax.devices()[0].device_kind
        out.append(row)
        print(json.dumps(row), flush=True)
    return out


def served_held(config: dict, seeds: list[int], prompts: int,
                deadline_s: int) -> dict:
    """A short served run with the held pairs of every share call counted."""
    import faulthandler
    import random

    import jax
    import numpy as np

    from benchmark import serve, traffic
    from scripts.moe_grouped_sweep import cell_calls
    from vllm_omni_tpu.config.stage import StageConfig
    from vllm_omni_tpu.entrypoints.openai.api_server import build_server
    from vllm_omni_tpu.ops import moe

    seen: list[tuple[int, int]] = []  # (held pairs of a share call, pairs)
    real = moe._grouped_mlp

    def counted(xs, gate_up, down, group_sizes, *rest):
        jax.debug.callback(
            lambda held, rows=xs.shape[0]: seen.append((int(held), rows)),
            group_sizes.sum())
        return real(xs, gate_up, down, group_sizes, *rest)

    moe._grouped_mlp = counted
    # a step that never returns would hold the machine to the call's limit
    faulthandler.dump_traceback_later(deadline_s, exit=True)
    stage = StageConfig(
        stage_id=0, stage_type="llm",
        engine_args={
            "model_factory": serve.model_factory,
            "model_factory_args": {"config": config, "seed": seeds[0],
                                   "timings": {}},
            "warmup": False, **config["engine"]},
        engine_input_source=[-1], final_output=True,
        final_output_type="text")
    server, state = build_server(model="benchmark", stage_configs=[stage],
                                 host="127.0.0.1", port=0)
    try:
        engine = state.omni._omni.stages[0].engine
        limit = config["engine"]["max_model_len"] - 16
        chunk = config["engine"]["max_num_batched_tokens"]
        for i, seed in enumerate(seeds):
            if i:
                old, engine.runner.params = engine.runner.params, None
                for leaf in jax.tree.leaves(old):
                    leaf.delete()
                engine.runner.params = serve.model_factory(
                    config, seed, {})[0]
            rng = random.Random(f"{seed}:share-rows")
            for _ in range(prompts):
                ids = [3 + ord(c) for c in rng.choices(
                    traffic.ALPHABET, k=min(4 * chunk, limit))]
                state.collect({"prompt_token_ids": ids},
                              {"max_tokens": 8, "temperature": 0.0,
                               "ignore_eos": True})
        jax.effects_barrier()
    finally:
        faulthandler.cancel_dump_traceback_later()
        moe._grouped_mlp = real
        state.shutdown()
        server.server_close()
    _, _, width, e, _, _ = next(iter(cell_calls(config).values()))
    by_pairs: dict[int, list[int]] = {}
    for held, pairs in seen:
        by_pairs.setdefault(pairs, []).append(held)
    by_pairs = {pairs: {
        "cap": cap, "calls": len(v),
        "largest_held_x_mean": max(v) * width / (pairs * e),
        "mean_held_x_mean": float(np.mean(v)) * width / (pairs * e),
        "largest_held_over_cap": max(v) / cap,
        "calls_over_cap": sum(h > cap for h in v)}
        for pairs, v in sorted(by_pairs.items())
        for cap in [share_cap(pairs, e, width)]}
    return {"config": config["name"], "seeds": seeds, "held_of": [e, width],
            "share_calls": len(seen), "by_pairs": by_pairs,
            "device": jax.devices()[0].device_kind}


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--plan", action="store_true")
    ap.add_argument("--served", action="store_true")
    ap.add_argument("--probe", metavar="FORM")
    ap.add_argument("--only", default="", help="comma-separated substrings")
    ap.add_argument("--forms", default="parent,tree,windows,cond,first")
    ap.add_argument("--reps", type=int, default=20)
    ap.add_argument("--seeds", type=int, nargs="+",
                    default=[5300000101, 5300000202])
    ap.add_argument("--prompts", type=int, default=2)
    ap.add_argument("--deadline-s", type=int, default=600)
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--tiny-config")
    ns, rest = ap.parse_known_args()
    if ns.plan:
        lines = [ln for c in share_configs(ns.only) for ln in plan(c)]
        print(plan_table(lines))
        return
    if ns.probe:
        from scripts import warmup_probe
        from vllm_omni_tpu.ops import moe

        moe.routed_moe_share = share_form(ns.probe)
        sys.argv[1:] = rest
        warmup_probe.main()
        return
    if rest:
        ap.error(f"unrecognized arguments: {rest}")

    import jax
    import jax.numpy as jnp

    os.makedirs(ROOT / "chiprun_out", exist_ok=True)
    on_tpu = jax.devices()[0].platform == "tpu"
    assert on_tpu or ns.tiny or ns.tiny_config, "times come from the chip only"
    if ns.served or ns.tiny_config:
        configs = ([json.loads(Path(ns.tiny_config).read_text())]
                   if ns.tiny_config else share_configs(ns.only))
        # a process a configuration: two models do not fit a chip, and an
        # engine's threads outlive its shutdown
        if len(configs) != 1:
            raise SystemExit("--served takes one configuration a process: "
                             f"--only one of {[c['name'] for c in configs]}")
        row = served_held(configs[0], ns.seeds, ns.prompts, ns.deadline_s)
        print(json.dumps(row), flush=True)
        with open(ROOT / "chiprun_out" /
                  f"moe_share_served_{row['config']}.json", "w") as f:
            json.dump(row, f, indent=1)
        sys.stdout.flush()
        os._exit(0)
    rows = []
    for d in ([TINY] if ns.tiny else
              [dims_of(c) for c in share_configs(ns.only)]):
        rows += time_forms(d, ns.forms.split(","), ns.reps,
                           jnp.float32 if ns.tiny else jnp.bfloat16)
    with open(ROOT / "chiprun_out" / "moe_share_rows_sweep.json", "w") as f:
        json.dump({"rows": rows}, f, indent=1)


if __name__ == "__main__":
    main()
