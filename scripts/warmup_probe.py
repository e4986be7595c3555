"""``benchmark/run.py`` with the warm-up's progress printed and a deadline
an executable: for the first run of a new configuration on the chip.

    chiprun --timeout 1500 -- python3 -m scripts.warmup_probe \
        --workload lfm2_assist_sat --seed 3900000101 --seconds 45 --trace 2

The arguments are ``run.py``'s; ``PROBE_DEADLINE_S`` (420) is the longest
one executable may take to compile and run once.  ``run.py`` prints
nothing between its first line and the end of the warm-up, and a step that
never returns on the device holds the machine until the call's limit: PR
39's first run cost 45 chip-minutes that way and said nothing.  Here every
executable prints a line as it starts (to ``chiprun_out/warmup_probe.log``
too, which comes back when the call is killed), and one that outlasts the
deadline dumps every thread's stack and ends the process with code 1, so
the last line names the executable that stopped.  The probe counts
nothing itself (PR 52): each line also says what the executable before it
took, from the program's own record of it (``compile_stats.executables``;
``compile.current`` on ``/debug/z`` names the one being built), and the
line that ends the warm-up prints the ``startup`` spans so far and the
``compile`` totals: tracing, lowering, compiling or loading, first runs
(PERF.md section 5).  ``PROBE_ONLY_T=256,512`` warms those token buckets'
unified executables alone (with ``PROBE_ONLY_B=64`` also those decode
buckets' two executables each, and the largest bucket's) and ends the
process there: what a new kernel's first step programs cost to try.  A
builder's tool: the driver reads none of it.
"""

from __future__ import annotations

import faulthandler
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))
DEADLINE_S = int(os.environ.get("PROBE_DEADLINE_S", "420"))
ONLY_T = {int(t) for t in os.environ.get("PROBE_ONLY_T", "").split(",") if t}
ONLY_B = {int(b) for b in os.environ.get("PROBE_ONLY_B", "").split(",") if b}
_T0 = time.monotonic()


def say(msg: str) -> None:
    line = f"[probe {time.monotonic() - _T0:7.1f}s] {msg}"
    print(line, flush=True)
    out = ROOT / "chiprun_out"
    out.mkdir(exist_ok=True)
    with open(out / "warmup_probe.log", "a") as f:
        f.write(line + "\n")


def main() -> None:
    from vllm_omni_tpu.engine import llm_engine
    from vllm_omni_tpu.introspection import phase_clock

    warm = llm_engine.LLMEngine.warmup

    def record(stats) -> str:
        """The program's own account of the set-up so far."""
        snap = stats.snapshot()
        spans = {s["name"]: s["ms"]
                 for s in phase_clock.startup.snapshot()["spans"]}
        rows = snap.pop("executables")
        return (f"spans (ms) {spans}; compile {snap}; slowest "
                f"{max(rows, key=lambda r: r['total_s'], default=None)}")

    def warmup(self, prefill_shapes=(), progress_fn=None):
        runner = self.runner

        def progress(msg: str) -> None:
            faulthandler.cancel_dump_traceback_later()
            faulthandler.dump_traceback_later(DEADLINE_S, exit=True)
            done = runner.compile_stats.executables
            say(f"{msg}  (before it: {done[-1] if done else None})")

        say(f"warm-up begins: decode buckets {runner._batch_buckets}, "
            f"token buckets {runner._token_buckets} up to the first over "
            f"{runner._warm_token_cap}")
        if ONLY_T or ONLY_B:
            runner._token_buckets = tuple(
                t for t in runner._token_buckets if t in ONLY_T)
            runner._warm_token_cap = max(ONLY_T, default=0)
            # the largest decode bucket stays: it is the unified step's
            # count of sequences
            runner._batch_buckets = tuple(
                b for b in runner._batch_buckets
                if b in ONLY_B or b == runner._batch_buckets[-1])
            built = runner.precompile(decode=bool(ONLY_B),
                                      progress_fn=progress)
            faulthandler.cancel_dump_traceback_later()
            say(f"PROBE_ONLY_T: {built} executables returned; "
                f"{record(runner.compile_stats)}")
            sys.stdout.flush()
            os._exit(0)
        built = warm(self, prefill_shapes=prefill_shapes,
                     progress_fn=progress)
        faulthandler.cancel_dump_traceback_later()
        say(f"warm-up ended: {built} executables; "
            f"{record(runner.compile_stats)}")
        return built

    llm_engine.LLMEngine.warmup = warmup
    from benchmark import run

    run.main()


if __name__ == "__main__":
    main()
