"""Introspection: the debugging spine of the serving stack.

Five faces (docs/debugging.md):

- **flight recorder** — bounded ring of per-step records appended from
  ``LLMEngine``'s step paths with zero device syncs, dumped as JSON on
  crash / SIGUSR2 / watchdog trip / demand (flight_recorder.py);
- **stall watchdog** — monitor thread that distinguishes XLA-compile
  stalls from true hangs and captures stacks + request tables + step
  tails on trip (watchdog.py);
- **/debug/z** — live JSON views served by the OpenAI server
  (debugz.py);
- **device-memory ledger** — per-component HBM accounting with peak
  watermarks, CPU-deterministic fallback (memory_ledger.py);
- **phase clock** — the engine thread's ``omni.*`` phases on the host's
  clock on every step, the step's host/device split, and the stalls of
  a second and more caught where they happen (phase_clock.py).

This module owns the process-global engine registry: engines register
at construction (weakly — registration must never extend an engine's
lifetime), and the crash hooks / watchdog / debug endpoints enumerate
the live ones at dump time.
"""

from __future__ import annotations

import threading
import weakref

from vllm_omni_tpu.introspection.debugz import request_table
from vllm_omni_tpu.introspection.flight_recorder import (
    FlightRecorder,
    build_dump,
    capture_stacks,
    dump_to_file,
    install_crash_hooks,
)
from vllm_omni_tpu.introspection.memory_ledger import DeviceMemoryLedger
from vllm_omni_tpu.introspection.phase_clock import PhaseClock
from vllm_omni_tpu.introspection.watchdog import StallWatchdog

__all__ = [
    "FlightRecorder",
    "DeviceMemoryLedger",
    "PhaseClock",
    "StallWatchdog",
    "build_dump",
    "capture_stacks",
    "dump_to_file",
    "install_crash_hooks",
    "register_engine",
    "iter_engines",
    "request_table",
]

_engines: "weakref.WeakSet" = weakref.WeakSet()
_registry_lock = threading.Lock()


def register_engine(engine) -> None:
    """Track a live engine for crash dumps / watchdog trips / debugz.
    Also installs the process crash hooks on first use (they no-op
    without ``OMNI_TPU_FLIGHT_DIR``)."""
    with _registry_lock:
        _engines.add(engine)
    install_crash_hooks(_live_recorders)


def iter_engines() -> list:
    """The live registered engines, stage-ordered (stable for dumps)."""
    with _registry_lock:
        engines = list(_engines)
    return sorted(engines,
                  key=lambda e: (getattr(e, "stage_id", 0) or 0, id(e)))


def _live_recorders() -> list[FlightRecorder]:
    return [e.flight for e in iter_engines()
            if getattr(e, "flight", None) is not None]
