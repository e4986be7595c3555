"""Readers of per-layer metrics.  A metric is a file
``layer_metrics/<name>.json`` that names one reader here (``"reader"``)
and gives its parameters; a metric that needs a new kind of reader adds a
module and names it ``"module:function"``.

A reader takes the run's observations and returns a number, or None where
it finds nothing to read; the harness then leaves the metric out.

``obs`` holds: ``records`` and ``window`` (the load generator's records and
the measured window on its clock), ``engine0``/``engine1`` (the engine's
``/metrics?format=json`` snapshot at the window's start and end),
``steps`` (flight-recorder records of the window, or of the traced part of
it when a trace was taken), ``compiles`` (compilations inside the window),
``trace`` (the reduced device trace, or None), ``config``, ``traffic``,
``device`` and ``chips``.
"""

from __future__ import annotations

import fnmatch
import importlib

from benchmark import e2e, spec, trace, traffic


def resolve(reader: str):
    module, _, fn = reader.rpartition(":")
    return getattr(importlib.import_module(module or __name__), fn)


def histogram_mean(obs: dict, histogram: str) -> float | None:
    """Mean of a host-clock histogram over the window: the difference of
    its sum over the difference of its count between the two snapshots."""
    h0, h1 = obs["engine0"][histogram], obs["engine1"][histogram]
    n = h1["count"] - h0["count"]
    return (h1["sum"] - h0["sum"]) / n if n > 0 else None


def gen_late_p99_ms(obs: dict) -> float | None:
    w0, w1 = obs["window"]
    late = [(r["sent"] - r["due"]) * 1e3
            for r in e2e.due_in_window(obs["records"], w0, w1)]
    return e2e.nearest_rank(late, 0.99) if late else None


def front_ms_mean(obs: dict) -> float | None:
    """The client's mean time from due to first streamed content, minus the
    engine's mean time from arrival to first token: what the HTTP handler,
    the orchestrator and the stream add in front of and behind the engine."""
    w0, w1 = obs["window"]
    client = [(r["events"][0] - r["due"]) * 1e3
              for r in e2e.due_in_window(obs["records"], w0, w1)
              if r["status"] == "ok"]
    engine = histogram_mean(obs, "ttft_ms")
    if not client or engine is None:
        return None
    return sum(client) / len(client) - engine


def rows_per_step_mean(obs: dict) -> float | None:
    rows = [s["prefills"] + s["decodes"] for s in obs["steps"]
            if s["prefills"] + s["decodes"] > 0]
    return sum(rows) / len(rows) if rows else None


def padding_eff_pct(obs: dict) -> float | None:
    p0, p1 = obs["engine0"]["padding"], obs["engine1"]["padding"]
    padded = p1["padded_tokens_total"] - p0["padded_tokens_total"]
    useful = p1["useful_tokens_total"] - p0["useful_tokens_total"]
    return 100.0 * useful / padded if padded > 0 else None


def window_compiles(obs: dict) -> float:
    return float(obs["compiles"])


def device_op_share_pct(obs: dict, patterns: list[str]) -> float | None:
    """Share of the device's operation time (self time, so that a loop or
    a conditional is not counted with its body) spent in the groups whose
    printed name matches one of ``patterns``."""
    tr = obs["trace"]
    if not tr or not tr["self_s"]:
        return None
    total = sum(tr["self_s"].values())
    hit = sum(v for k, v in tr["self_s"].items()
              if any(fnmatch.fnmatchcase(k, p) for p in patterns))
    return 100.0 * hit / total


def collective_share_pct(obs: dict) -> float | None:
    return device_op_share_pct(obs, list(trace.COLLECTIVES))


def device_idle_pct(obs: dict) -> float | None:
    tr = obs["trace"]
    if not tr or tr["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])


def device_roofline_pct(obs: dict) -> float | None:
    """The least time the traced steps need on this chip's published
    peaks, over the time the device was busy in the trace."""
    tr = obs["trace"]
    if not tr or tr["busy_s"] <= 0 or not obs["steps"]:
        return None
    pairs = traffic.multiset(obs["traffic"], 512)
    p_sum = sum(p for p, _ in pairs)
    mean_prefill_ctx = sum(p * p for p, _ in pairs) / (2.0 * p_sum)
    mean_decode_ctx = (sum(o * (p + o / 2.0) for p, o in pairs)
                       / sum(o for _, o in pairs))
    arch, config = spec.arch_of(obs["config"]), obs["config"]
    least = trace.least_seconds(
        (arch.step_cost(s, config, mean_prefill_ctx, mean_decode_ctx)
         for s in obs["steps"]),
        trace.peaks_of(obs["device"]["kind"]), chips=obs["chips"])
    return 100.0 * least / tr["busy_s"]
