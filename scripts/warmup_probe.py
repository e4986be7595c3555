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
the last line names the executable that stopped.  The line that ends the
warm-up says what it was made of: the seconds JAX itself reports for
tracing (``jaxpr_trace_duration``), for lowering
(``jaxpr_to_mlir_module_duration``) and for compiling or loading from the
persistent cache (``backend_compile_duration``), summed over the warm-up
(PERF.md, PR 48: a warm set-up is the first two).  ``PROBE_ONLY_T=256,512``
warms those token buckets' unified executables alone (with
``PROBE_ONLY_B=64`` also those decode buckets' two executables each, and
the largest bucket's) and ends the process there: what a new kernel's
first step programs cost to try, a minute of weights and half a minute an
executable.  A builder's tool: the driver
reads none of it.
"""

from __future__ import annotations

import collections
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

    warm = llm_engine.LLMEngine.warmup

    def progress(msg: str) -> None:
        faulthandler.cancel_dump_traceback_later()
        faulthandler.dump_traceback_later(DEADLINE_S, exit=True)
        say(msg)

    import jax

    parts: collections.Counter = collections.Counter()  # event -> seconds

    def add(event, secs, **kw):
        parts[event] += secs

    jax.monitoring.register_event_duration_secs_listener(add)

    def warmup(self, prefill_shapes=(), progress_fn=None):
        runner = self.runner
        parts.clear()
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
            say(f"PROBE_ONLY_T: {built} executables returned, "
                f"{runner.compile_stats}")
            sys.stdout.flush()
            os._exit(0)
        built = warm(self, prefill_shapes=prefill_shapes,
                     progress_fn=progress)
        faulthandler.cancel_dump_traceback_later()
        say(f"warm-up ended: {built} executables, {runner.compile_stats}")
        say("warm-up by part: " + ", ".join(
            f"{event.rsplit('/', 1)[-1]} {secs:.2f} s"
            for event, secs in sorted(parts.items())))
        return built

    llm_engine.LLMEngine.warmup = warmup
    from benchmark import run

    run.main()


if __name__ == "__main__":
    main()
