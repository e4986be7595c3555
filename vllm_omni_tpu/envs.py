"""Environment-variable registry.

Equivalent in role to the reference's ``vllm_omni/diffusion/envs.py:19`` env
registry: one module that owns every environment knob, with typed accessors,
so flags are discoverable and greppable.
"""

from __future__ import annotations

import os
from typing import Callable, Optional

# name -> (default, parser)
_ENV_REGISTRY: dict[str, tuple[str, Callable[[str], object]]] = {}


def _register(name: str, default: str, parser: Callable[[str], object]):
    _ENV_REGISTRY[name] = (default, parser)


def _get(name: str):
    default, parser = _ENV_REGISTRY[name]
    return parser(os.environ.get(name, default))


_bool = lambda s: s.lower() in ("1", "true", "yes", "on")

# Attention backend override for DiT stages (reference:
# DIFFUSION_ATTENTION_BACKEND, attention/selector.py:77). Values:
# "pallas_flash", "xla", "auto".
_register("OMNI_TPU_DIFFUSION_ATTENTION_BACKEND", "auto", str)
# Attention backend for AR paged attention: "pallas_paged", "xla", "auto".
_register("OMNI_TPU_AR_ATTENTION_BACKEND", "auto", str)
# Force interpret mode for pallas kernels (CPU testing).
_register("OMNI_TPU_PALLAS_INTERPRET", "0", _bool)
# Directory for jax profiler traces (reference: VLLM_TORCH_PROFILER_DIR).
_register("OMNI_TPU_PROFILER_DIR", "", str)
# Stats jsonl output (reference: --log-stats).
_register("OMNI_TPU_STATS_DIR", "", str)
# Per-request trace output path PREFIX ({prefix}.trace.jsonl +
# {prefix}.trace.json Chrome trace) — the env face of Omni(trace_path=).
_register("OMNI_TPU_TRACE_PATH", "", str)
# Connector backend default for single-node stage transfer.
_register("OMNI_TPU_CONNECTOR", "shm", str)
# Per-stage logging prefix.
_register("OMNI_TPU_LOGGING_PREFIX", "", str)
# Root log level for the package logger.
_register("OMNI_TPU_LOG_LEVEL", "INFO", str)
# RNG seed default.
_register("OMNI_TPU_SEED", "0", int)
# Default end-to-end request deadline in seconds (0 = unbounded); per
# call / per request values override (resilience/deadline.py).
_register("OMNI_TPU_DEFAULT_DEADLINE_S", "0", float)
# Fault-injection plan, e.g. "seed=42;stage1:kill_after=2;conn:drop_pct=0.2"
# (resilience/faults.py grammar).  Inherited by spawned stage workers.
_register("OMNI_TPU_FAULTS", "", str)
# Flight-recorder dump directory (introspection/flight_recorder.py):
# crash/SIGUSR2/watchdog dumps land here as JSON; empty disables the
# file-writing face (the in-memory ring and /debug endpoints stay on).
_register("OMNI_TPU_FLIGHT_DIR", "", str)
# Per-engine flight-recorder ring capacity (step records kept).
_register("OMNI_TPU_FLIGHT_CAPACITY", "256", int)
# Alert-engine evaluation interval in seconds (metrics/alerts.py):
# > 0 starts the evaluation thread over the default burn-rate/overload
# rule set.  0 (default) builds the engine without the thread — tests
# and operators drive evaluate_once() directly, and /debug/alerts
# still answers.
_register("OMNI_TPU_ALERTS_S", "0", float)
# Per-reason flight-dump cooldown in seconds (introspection/
# flight_recorder.py DumpCooldown): repeated dumps with the same
# reason into the same OMNI_TPU_FLIGHT_DIR within the window are
# suppressed (and counted) — a flapping alert or a held-down SIGUSR2
# must not flood the incident directory.  0 disables the limit.
_register("OMNI_TPU_DUMP_COOLDOWN_S", "30", float)
# Stall-watchdog deadline in seconds (introspection/watchdog.py): a
# busy engine making no step progress for this long — with no XLA
# compile in flight — trips the watchdog (dump + /health 503).
# 0 disables the monitor thread (the default: a compile legitimately
# stalls the engine for tens of seconds, so the deadline is a
# deployment decision).
_register("OMNI_TPU_WATCHDOG_S", "0", float)


def __getattr__(name: str):
    if name in _ENV_REGISTRY:
        return _get(name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


def env_names() -> list[str]:
    return sorted(_ENV_REGISTRY)
