"""Readers of per-layer metrics whose source a program may lack.

A metric that a later PR adds to a cell the benchmark already had is read
on the parent of that PR too, whose program has no such histogram or
counter: the reader then finds nothing and says so (None: the harness
leaves the metric out), where ``readers.histogram_mean`` would raise on
the missing key.
"""

from __future__ import annotations

from benchmark import readers


def histogram_mean(obs: dict, histogram: str) -> float | None:
    """``readers.histogram_mean``; None where either snapshot of the
    engine lacks the histogram."""
    if any(histogram not in obs[snap] for snap in ("engine0", "engine1")):
        return None
    return readers.histogram_mean(obs, histogram)
