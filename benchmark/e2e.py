"""End-to-end metrics from the load generator's records: host clock, the
client's side of the HTTP server, nothing read from the program.

A record carries ``prompt_tokens``, ``output_tokens``, ``sent``, ``done``,
``events`` (the instants of the streamed content events), ``status`` and,
in an open loop, ``due``.  The window is ``(w0, w1)`` on the same clock.
"""

from __future__ import annotations

import math


def nearest_rank(xs: list[float], p: float) -> float:
    """Nearest-rank percentile: the value at index ceil(p*n) - 1 (copied
    from vllm_omni_tpu/metrics/stats.py nearest_rank_pct)."""
    xs = sorted(xs)
    return xs[max(math.ceil(p * len(xs)) - 1, 0)]


def _overlap(a0: float, a1: float, w0: float, w1: float) -> float:
    return max(min(a1, w1) - max(a0, w0), 0.0)


#: the part of a request's life, from the start, over which its prompt is
#: laid: the prefill is the first step or steps after it is sent
PREFILL_SHARE = 0.1


def tok_s(records: list[dict], w0: float, w1: float) -> float | None:
    """Tokens served per second of the window, over all the work and all
    the time of the window.  The server streams nothing before a reply is
    whole, so the client sees tokens only at completions; counting whole
    replies at their completion lets the ~64 requests that straddle each
    edge move the rate by some percent.  So a request's work is laid on
    the clock as it is done: its prompt evenly over the first tenth of its
    life (a closed loop sends into a free slot, and the prefill is the next
    step or steps; laid on the one instant of sending, a prompt of 1024
    tokens that a seed moves across an edge is 1% of a 45 s window), its
    output tokens evenly over all of it."""
    tokens = 0.0
    for r in records:
        if r["status"] != "ok":
            continue
        life = r["done"] - r["sent"]
        head = PREFILL_SHARE * life
        tokens += (r["prompt_tokens"]
                   * _overlap(r["sent"], r["sent"] + head, w0, w1) / head
                   + r["output_tokens"]
                   * _overlap(r["sent"], r["done"], w0, w1) / life)
    return tokens / (w1 - w0) if tokens else None


def due_in_window(records: list[dict], w0: float, w1: float) -> list[dict]:
    return [r for r in records if w0 <= r["due"] < w1]


def ttft_p90_ms(records: list[dict], w0: float, w1: float) -> float | None:
    """90th percentile, over the requests due inside the window, of the
    first streamed content minus the instant the request was due.  A
    request that failed has no first token and makes the run incorrect;
    it is not quietly left out of the tail."""
    xs = [(r["events"][0] - r["due"]) * 1e3
          for r in due_in_window(records, w0, w1) if r["status"] == "ok"]
    return nearest_rank(xs, 0.90) if xs else None


def ms_per_token_p50(records: list[dict], w0: float, w1: float
                     ) -> float | None:
    """Median, over the requests due inside the window, of the whole reply
    time from the due instant divided by the tokens generated: what one
    output token costs a chat user below capacity."""
    xs = [(r["done"] - r["due"]) * 1e3 / r["output_tokens"]
          for r in due_in_window(records, w0, w1) if r["status"] == "ok"]
    return nearest_rank(xs, 0.50) if xs else None


#: end-to-end metric name -> (records, w0, w1) -> value; ``setup_s`` is
#: the harness's own clock and is not here
METRICS = {"tok_s": tok_s, "ttft_p90_ms": ttft_p90_ms,
           "ms_per_token_p50": ms_per_token_p50}
