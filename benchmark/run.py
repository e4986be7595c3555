"""One run of one cell of BENCHMARK.json.

    python3 benchmark/run.py --workload NAME --seed N --seconds S --trace 0|1|2

Set-up (weights from the seed, warm-up of every shape, the reference check,
the lead-in) is timed as ``setup_s``; then the window is measured for
``--seconds``.  The last line of standard output is one JSON object.  A
cell never runs on a CPU: without a TPU, or with fewer chips than the cell
asks for, the exit code is not 0 and no result is printed.

``--trace 0`` measures and reports the end-to-end metrics.  ``--trace 1``
traces three seconds inside the measured window, in a run of its own, and
reports the per-layer metrics.  ``--trace 2`` is a ``--trace 0`` run that
goes on once its window has closed and what was in flight has run to its
end: it offers the same traffic again and traces a few seconds of that
tail through the program's own control (``start_profile`` /
``stop_profile``), so that its last line holds both kinds of metric and
the end-to-end numbers are ``--trace 0``'s by construction.
"""

from __future__ import annotations

import time

_T_START = time.monotonic()  # before the heavy imports: setup_s counts them

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))

from benchmark import e2e, readers, spec, trace  # noqa: E402

#: build outputs of the benchmark, at a fixed path inside the checkout (the
#: path is part of the compile cache's key); listed in .gitignore
CACHE = ROOT / ".benchmark_cache"
#: seconds of the window that a ``--trace 1`` run traces, and where they
#: start: traces are large and tracing slows the host
TRACE_AFTER_S, TRACE_SECONDS = 4.0, 3.0
#: the traced tail of a ``--trace 2`` run: the traffic's own lead-in, then
#: a window of this many seconds of which ``TRACE_SECONDS`` are traced from
#: ``TAIL_TRACE_AFTER_S`` on; the rest covers the time the profiler takes
#: to stop, so that the generator is still sending while it is traced
TAIL_SECONDS, TAIL_TRACE_AFTER_S = 5.0, 1.0


def say(msg: str) -> None:
    print(f"[benchmark {time.monotonic() - _T_START:7.1f}s] {msg}",
          flush=True)


def sleep_until(t: float) -> None:
    while (wait := t - time.monotonic()) > 0:
        time.sleep(min(wait, 0.5))


def device_gate(chips: int, allow_cpu: bool) -> dict:
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" and not allow_cpu:
        raise SystemExit(f"benchmark: no TPU: JAX found "
                         f"{devices[0].platform!r}; a cell never runs there")
    if len(devices) < chips:
        raise SystemExit(f"benchmark: the cell asks for {chips} chips, JAX "
                         f"found {len(devices)}")
    if devices[0].platform == "tpu":
        trace.peaks_of(devices[0].device_kind)  # an unlisted kind is an error
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}


def prepare_jax() -> None:
    """Before JAX and the program are imported: the persistent compile
    cache at a fixed path inside the checkout (or where the environment
    says), every program kept in it, and a flight-recorder ring that holds
    a whole window's steps."""
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", str(CACHE / "jax"))
    os.environ["OMNI_TPU_FLIGHT_CAPACITY"] = "16384"
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def find_problems(records: list, connect_ms: list, check: dict,
                  compiles_in_window: int, before: dict, after: dict,
                  device: dict) -> list[str]:
    """What makes the run incorrect; also says what the load was."""
    from vllm_omni_tpu.ops import _dispatch

    failed = [r for r in records if r["status"] != "ok"]
    drawn = {"prefill_tokens": sum(r["prompt_tokens"] for r in records),
             "tokens_generated": sum(r["output_tokens"] for r in records)}
    counted = {k: after["counters"][k] - before["counters"][k]
               for k in drawn}
    late = [(r["sent"] - r["due"]) * 1e3 for r in records if "due" in r]
    say(f"load: {len(records)} requests sent, {len(failed)} failed, "
        f"{len(connect_ms)} connections (connect max "
        f"{max(connect_ms):.1f} ms), generator late p99 "
        f"{e2e.nearest_rank(late, 0.99) if late else 0.0:.2f} ms; engine "
        f"counted {counted}, drawn {drawn}")
    problems = []
    if not check["ok"]:
        problems.append(f"reference check: {check}")
    if compiles_in_window:
        problems.append(f"{compiles_in_window} compilations in the window")
    if _dispatch.native_fallbacks:
        problems.append(f"ops fell back to XLA: "
                        f"{dict(_dispatch.native_fallbacks)}")
    if failed:
        problems.append(f"{len(failed)} requests failed, e.g. {failed[0]}")
    elif counted != drawn:
        problems.append(f"token counts: engine {counted}, drawn {drawn}")
    if device["platform"] != "tpu":
        problems.append("not a TPU")
    for p in problems:
        say(f"NOT CORRECT: {p}")
    return problems


def trace_tail(state, port: int, traffic: dict, args, template: int
               ) -> dict:
    """The traced tail of a ``--trace 2`` run, after the measured window
    has closed and what was in flight has run to its end: the same traffic
    file offered again (its own lead-in, ``TAIL_SECONDS`` of window, other
    message bytes, so that the prefix cache holds none of them), of which
    ``TRACE_SECONDS`` are traced through the program's own control.  The
    profiler's first start in a process costs seconds, so it is started
    and stopped once before, on an idle server, and that trace is thrown
    away.  Returns the tail's load and the traced interval (wall clock),
    with the directory the trace landed in."""
    from benchmark import serve

    trace_dir = CACHE / "trace" / args.workload
    shutil.rmtree(trace_dir, ignore_errors=True)
    t = time.monotonic()
    state.omni.start_profile(str(trace_dir / "first_start"))
    state.omni.stop_profile()
    shutil.rmtree(trace_dir, ignore_errors=True)
    say(f"tail: the profiler's first start and stop took "
        f"{time.monotonic() - t:.1f} s (that trace is thrown away)")
    traced: dict = {}

    def trace_part(w0: float) -> None:
        sleep_until(w0 + TAIL_TRACE_AFTER_S)
        t_start = time.monotonic()
        state.omni.start_profile(str(trace_dir))
        traced["lo"] = time.time()
        sleep_until(time.monotonic() + TRACE_SECONDS)
        traced["hi"], t_stop = time.time(), time.monotonic()
        traced["dir"] = state.omni.stop_profile()
        say(f"tail: traced {traced['hi'] - traced['lo']:.2f} s from "
            f"{t_start - w0:.2f} s into the tail's window; stopping and "
            f"writing took {time.monotonic() - t_stop:.1f} s")

    load = serve.drive(port, traffic, args.seed + 1, TAIL_SECONDS, template,
                       in_window=trace_part)
    if traced.get("dir") is None:
        raise SystemExit("benchmark: the program's stop_profile named no "
                         "directory: nothing was traced")
    return {**load, **traced}


def run_cell(args) -> dict:
    cell = spec.resolve_cell(args.workload, Path(args.bench_root))
    config, traffic = cell["config"], cell["traffic"]
    chips = cell["cell"]["chips"]
    prepare_jax()
    import jax

    device = device_gate(chips, args.allow_cpu)
    say(f"cell {args.workload}: config {cell['cell']['config']}, traffic "
        f"{cell['cell']['traffic']}, {chips} chip(s) of {device['count']} x "
        f"{device['kind']} ({device['platform']}), seed {args.seed}, "
        f"{args.seconds:g} s")

    compiles: list[float] = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, secs, **kw: compiles.append(time.monotonic())
        if event == "/jax/core/compile/backend_compile_duration" else None)

    from benchmark import serve

    timings: dict = {}
    server, state, engine = serve.start_server(config, args.seed, timings)
    port = server.server_address[1]
    serve_thread = threading.Thread(target=server.serve_forever, daemon=True)
    serve_thread.start()
    try:
        t = time.monotonic()
        check = serve.reference_check(state, engine, config, args.seed)
        template = serve.template_tokens(port, traffic)
        timings["reference_s"] = time.monotonic() - t
        say(f"setup: weights {timings['weights_s']:.1f} s, warm-up or cache "
            f"load {timings['warmup_s']:.1f} s "
            f"({engine.runner.compile_stats['compiles']} executables, "
            f"{len(compiles)} backend compilations so far), reference check "
            f"{timings['reference_s']:.1f} s, lead-in "
            f"{traffic['lead_in_s']:g} s")
        say(f"reference: max |served - float32| logprob "
            f"{check.get('max_abs_diff')} (tolerance {serve.LOGPROB_TOL}); "
            f"chat template adds {template} tokens")

        def trace_part(w0: float) -> None:
            trace_dir = CACHE / "trace" / args.workload
            shutil.rmtree(trace_dir, ignore_errors=True)
            sleep_until(w0 + min(TRACE_AFTER_S, args.seconds / 4))
            traced.append(time.time())
            jax.profiler.start_trace(str(trace_dir))
            sleep_until(time.monotonic() + min(TRACE_SECONDS,
                                               args.seconds / 2))
            jax.profiler.stop_trace()
            traced.append(time.time())

        traced: list[float] = []
        load = serve.drive(port, traffic, args.seed, args.seconds, template,
                           in_window=trace_part if args.trace == 1 else None)
        w0, w1 = load["window"]
        setup_s = w0 - _T_START
        compiles_in_window = sum(1 for t in compiles if w0 <= t < w1)
        tail = (trace_tail(state, port, traffic, args, template)
                if args.trace == 2 else None)
        memory_peak = max(
            (d.memory_stats() or {}).get("peak_bytes_in_use", 0)
            for d in jax.local_devices()[:chips])
    finally:
        server.shutdown()
        state.shutdown()
        server.server_close()
        serve_thread.join(timeout=10)

    records, connect_ms = load["records"], load["connect_ms"]
    engine0, engine1, steps = load["engine0"], load["engine1"], load["steps"]
    problems = find_problems(records, connect_ms, check, compiles_in_window,
                             load["engine_before"], load["engine_after"],
                             device)
    window = (w0, w1)
    edge = "due" if traffic["loop"] == "open" else "sent"
    in_window = [r for r in records if w0 <= r[edge] < w1]
    metrics: dict = {}
    device_out = {**device, "memory_peak_bytes": memory_peak}
    result = {"correct": not problems, "attempted": len(in_window),
              "failed": sum(1 for r in in_window if r["status"] != "ok"),
              "metrics": metrics, "device": device_out}
    detail = {"records": records, "window": window, "timings": timings,
              "connect_ms": connect_ms, "reference": check}
    if args.trace != 1:
        for m in cell["end_to_end"]:
            value = (setup_s if m["name"] == "setup_s"
                     else e2e.METRICS[m["name"]](records, w0, w1))
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    if not args.trace:
        keep_run(args, result, detail)
        return result

    if tail is not None:
        # counters and histograms read the measured window's snapshots,
        # untraced; the trace and the steps are the tail's
        lo, hi, steps = tail["lo"], tail["hi"], tail["steps"]
    else:
        lo, hi = traced
    reduced = trace.reduce_trace(CACHE / "trace" / args.workload)
    obs = {"records": records, "window": window, "engine0": engine0,
           "engine1": engine1, "compiles": compiles_in_window,
           "steps": [s for s in steps if lo <= s["ts"] <= hi],
           "trace": reduced, "config": config, "traffic": traffic,
           "device": device, "chips": chips}
    for m in cell["per_layer"]:
        value = readers.resolve(m["reader"])(obs, **m.get("params", {}))
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    if reduced:
        device_out["busy_s"] = reduced["busy_s"]
        device_out["window_s"] = reduced["window_s"]
        result["breakdown"] = {"device_ops": trace.top(reduced["self_s"]),
                               "idle_gaps": trace.top(reduced["gaps_s"])}
    if tail is not None:
        planes = tail_report(result, tail, compiles, w1)
        # the trace is reduced: the tables are kept, the file is not
        shutil.rmtree(CACHE / "trace" / args.workload, ignore_errors=True)
        detail["tail"] = {"window": tail["window"], "records": tail["records"],
                          "traced": (tail["lo"], tail["hi"]),
                          "planes": planes}
    keep_run(args, result, {**detail, "trace": reduced, "steps": obs["steps"],
                            "engine0": engine0, "engine1": engine1})
    return result


def tail_report(result: dict, tail: dict, compiles: list, w1: float
                ) -> dict | None:
    """What a ``--trace 2`` run says of its traced tail: idle gaps named by
    the host phase that covered them (``in_omni.schedule``; ``after_<op>``
    only where no annotation did), and what happened in the tail that the
    measured window's ``correct`` does not cover."""
    from benchmark import span_readers

    red = span_readers.reduction()
    if red and red["gaps_s"] and "breakdown" in result:
        result["breakdown"]["idle_gaps"] = trace.top(red["gaps_s"])
    if red and len(red["planes"]) > 1:
        for plane, own in sorted(red["planes"].items()):
            say(f"tail: {plane}: busy {own['busy_s']:.3f} s; " + ", ".join(
                f"{k or 'unnamed'} {v:.3f}" for k, v in trace.top(
                    own["scope_s"], 6)))
    if red and red["skew_ms"]:
        skew = sorted(red["skew_ms"])
        say(f"tail: one clock: a step's omni.readback ends "
            f"{skew[len(skew) // 2]:.2f} ms (median of {len(skew)}, "
            f"{skew[0]:.2f} to {skew[-1]:.2f}) after its last device "
            f"operation")
    records = tail["records"]
    said = {"compiles": sum(1 for t in compiles if t >= w1),
            "requests": len(records),
            "failed": sum(1 for r in records if r["status"] != "ok"),
            "traced_s": tail["hi"] - tail["lo"]}
    result.setdefault("breakdown", {})["tail"] = said
    say(f"tail: {said['requests']} requests sent, {said['failed']} failed, "
        f"{said['compiles']} compilations after the measured window")
    return red["planes"] if red else None


def keep_run(args, result: dict, detail: dict) -> None:
    """Leave the run's records and whole tables beside the cache, for the
    builder who wants more than the last line (the driver reads none)."""
    out = CACHE / "runs"
    out.mkdir(parents=True, exist_ok=True)
    name = (f"{args.workload}-seed{args.seed}-trace{args.trace}-"
            f"{int(time.time())}.json")
    with open(out / name, "w") as f:
        json.dump({"result": result, **detail}, f)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1, 2), default=0)
    # for rehearsals and tests only; the driver passes neither
    ap.add_argument("--bench-root", default=str(ROOT),
                    help="directory whose BENCHMARK.json and data files "
                         "are read (tests point it at a tiny cell)")
    ap.add_argument("--allow-cpu", action="store_true",
                    help="rehearse on a CPU: the result says so and is "
                         "never correct")
    args = ap.parse_args()
    result = run_cell(args)
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
