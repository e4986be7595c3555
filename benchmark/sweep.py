"""Find the knee of an open-loop mix: one set-up, a window at each rate.

    python3 benchmark/sweep.py --workload thinker_chat_rate \
        --rates 2,3,4,5 --seconds 40 --seed 1

A builder's tool, run once when a cell at a fixed rate is defined (and
again when an optimisation has moved the knee); the driver never runs it.
For each rate it prints the requests completed over those due, the time to
first streamed content by thirds of the window, and the engine's rows per
step and queue wait.  The knee is the highest rate at which every due
request is served and the last third's times are no higher than the
first's beyond the spread between runs; the cell then runs at four fifths
of it, written into the traffic file as a number.
"""

from __future__ import annotations

import argparse
import json
import sys
import threading
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))

from benchmark import e2e, readers, run, spec  # noqa: E402


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True,
                    help="requests per second, comma separated")
    ap.add_argument("--seconds", type=float, default=40.0)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seeds", default="",
                    help="a seed for each rate, comma separated (default: "
                         "--seed, --seed + 1, ...)")
    ap.add_argument("--bench-root", default=str(ROOT))
    ap.add_argument("--allow-cpu", action="store_true")
    args = ap.parse_args()
    cell = spec.resolve_cell(args.workload, Path(args.bench_root))
    run.prepare_jax()
    run.device_gate(cell["cell"]["chips"], args.allow_cpu)
    from benchmark import serve

    server, state, _ = serve.start_server(cell["config"], args.seed, {})
    port = server.server_address[1]
    thread = threading.Thread(target=server.serve_forever, daemon=True)
    thread.start()
    try:
        template = serve.template_tokens(port, cell["traffic"])
        rates = [float(r) for r in args.rates.split(",")]
        seeds = ([int(x) for x in args.seeds.split(",")] if args.seeds
                 else [args.seed + i for i in range(len(rates))])
        for rate, seed in zip(rates, seeds):
            traffic = {**cell["traffic"], "rate_per_s": rate}
            load = serve.drive(port, traffic, seed, args.seconds, template)
            w0, w1 = load["window"]
            due = e2e.due_in_window(load["records"], w0, w1)
            ok = [r for r in due if r["status"] == "ok"]
            third = (w1 - w0) / 3
            by_third = []
            for j in range(3):
                xs = [(r["events"][0] - r["due"]) * 1e3 for r in ok
                      if w0 + j * third <= r["due"] < w0 + (j + 1) * third]
                by_third.append([e2e.nearest_rank(xs, 0.5),
                                 e2e.nearest_rank(xs, 0.9)] if xs else None)
            row = {
                "rate_per_s": rate, "seed": seed, "due": len(due),
                "completed": len(ok),
                "ttft_p90_ms": e2e.ttft_p90_ms(load["records"], w0, w1),
                "ttft_p50_p90_ms_by_third": by_third,
                "ms_per_token_p50": e2e.ms_per_token_p50(load["records"],
                                                         w0, w1),
                "tok_s": e2e.tok_s(load["records"], w0, w1),
                "gen_late_p99_ms": readers.gen_late_p99_ms(
                    {"records": load["records"], "window": (w0, w1)}),
                "connect_max_ms": max(load["connect_ms"]),
                "rows_per_step_mean": readers.rows_per_step_mean(load),
                "queue_wait_mean_ms": readers.histogram_mean(
                    load, "queue_wait_ms"),
                "engine_ttft_mean_ms": readers.histogram_mean(load,
                                                              "ttft_ms"),
                "step_ms_mean": readers.histogram_mean(load, "step_ms"),
                "waiting_at_end": load["engine1"]["gauges"]["num_waiting"],
                "running_at_end": load["engine1"]["gauges"]["num_running"],
            }
            print(json.dumps(row), flush=True)
    finally:
        server.shutdown()
        state.shutdown()
        server.server_close()
        thread.join(timeout=10)


if __name__ == "__main__":
    main()
