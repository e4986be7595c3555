"""One general traffic generator, driven by a file of parameters.

The file fixes the schedule: the i-th of N requests takes the (i + 1/2)/N
quantile of each length distribution (and, in an open loop, of the gap
distribution), paired and ordered by the file's own ``order_seed``, as a
recorded trace is replayed.  ``--seed`` draws only the message bytes and
the sampling seeds (and, in the harness, the weights), so every seed
offers the same work in the same order: a served path this deep relaxes
over tens of seconds, and a reshuffled order moved a 45 s window's tail
by 10% and its rate by 2% (my chip runs, PR 24).  The order is stratified:
every eight consecutive requests hold one of each eighth of the lengths
(and of the gaps), so a window's edge cuts the same mix wherever it falls.

No JAX here: the load generator's process imports this module.
"""

from __future__ import annotations

import math
import random
from statistics import NormalDist

#: one byte, one token under the byte tokenizer; no control characters
ALPHABET = "".join(chr(c) for c in range(32, 127))


def quantile_lengths(dist: dict, n: int) -> list[int]:
    """N lengths at the mid-quantiles of a log-normal cut to [min, max]."""
    mu, sigma = math.log(dist["median"]), dist["sigma"]
    inv = NormalDist().inv_cdf
    return [int(round(min(max(math.exp(mu + sigma * inv((i + 0.5) / n)),
                              dist["min"]), dist["max"])))
            for i in range(n)]


def quantile_gaps(rate_per_s: float, n: int) -> list[float]:
    """N gaps at the mid-quantiles of an exponential distribution, scaled
    so that their mean is exactly 1/rate."""
    raw = [-math.log(1.0 - (i + 0.5) / n) for i in range(n)]
    scale = n / (rate_per_s * sum(raw))
    return [g * scale for g in raw]


def stratified(items: list, key, rng: random.Random, strata: int = 8) -> list:
    """A permutation of ``items`` in which every run of ``strata``
    consecutive items holds one item of each quantile band of ``key``, in
    an order of its own.  A plain shuffle leaves runs of long prompts, and
    a window that happens to cut one reads some percent more tokens than
    its neighbour."""
    ranked = sorted(items, key=key)
    n = len(ranked)
    bands = [ranked[n * b // strata:n * (b + 1) // strata]
             for b in range(strata)]
    for band in bands:
        rng.shuffle(band)
    out = []
    for j in range(max(len(b) for b in bands)):
        group = [b[j] for b in bands if j < len(b)]
        rng.shuffle(group)
        out += group
    return out


def multiset(traffic: dict, n: int) -> list[tuple[int, int]]:
    """The (prompt, output) pairs every run offers, paired at random by
    the file's ``order_seed``."""
    prompts = quantile_lengths(traffic["prompt_tokens"], n)
    outputs = quantile_lengths(traffic["output_tokens"], n)
    random.Random(traffic["order_seed"]).shuffle(outputs)
    return list(zip(prompts, outputs))


def requests_in_order(traffic: dict, seconds: float) -> list[dict]:
    """The run's requests in sending order: ``prompt_tokens``,
    ``output_tokens`` and, in an open loop, ``due_s`` from the start of
    the lead-in.  A closed loop cycles through the list."""
    rng = random.Random(traffic["order_seed"] + 1)
    if traffic["loop"] == "open":
        n = max(int(round(traffic["rate_per_s"]
                          * (traffic["lead_in_s"] + seconds))), 1)
    else:
        n = int(traffic["requests"])
    by = 1 if traffic.get("order_by") == "output_tokens" else 0
    pairs = stratified(multiset(traffic, n), lambda pair: pair[by], rng)
    reqs = [{"prompt_tokens": p, "output_tokens": o} for p, o in pairs]
    if traffic["loop"] == "open":
        gaps = stratified(quantile_gaps(traffic["rate_per_s"], n),
                          lambda g: g, rng)
        due = 0.0
        for r, g in zip(reqs, gaps):
            due += g
            r["due_s"] = due
    return reqs


def body(traffic: dict, seed: int, k: int, req: dict,
         template_tokens: int) -> dict:
    """The k-th request of the run as an OpenAI chat body.  The message is
    ``prompt_tokens - template_tokens`` seeded printable bytes, so the
    prompt has exactly the drawn length and nothing is shared between
    requests by accident."""
    rng = random.Random(f"{seed}:{k}")
    n_chars = req["prompt_tokens"] - template_tokens
    if n_chars < 1:
        raise ValueError(f"prompt of {req['prompt_tokens']} tokens is "
                         f"shorter than the template ({template_tokens})")
    return {
        "messages": [{"role": "user",
                      "content": "".join(rng.choices(ALPHABET, k=n_chars))}],
        "stream": True, "ignore_eos": True,
        "max_tokens": req["output_tokens"],
        "seed": rng.randrange(2 ** 31),
        **traffic["sampling"],
    }
