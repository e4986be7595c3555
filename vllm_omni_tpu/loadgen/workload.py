"""Arrival processes + scenario catalog for the open-loop harness.

Everything here is DETERMINISTIC given a seed: the same (seed, rate,
catalog) produces bit-identical arrival schedules, scenario picks,
prompt tokens, and output lengths — a serving-curve regression between
two builds can only come from the system under test, never from the
workload.  Nothing in this module touches jax or the network.

Arrival processes (the open-loop stance: offered load is a property of
the CLIENT population, so inter-arrival gaps are drawn up front and
never stretched by slow completions — the closed-loop alternative
flatters an overloaded server by self-throttling):

- ``poisson_arrivals``: exponential inter-arrival gaps at a target
  rate, the standard model for a large independent user population.
- ``trace_replay_arrivals``: replay explicit offsets (production logs,
  adversarial bursts), optionally time-scaled to sweep rates.
- ``diurnal_arrivals``: sinusoid-modulated Poisson — the compressed
  day/night cycle the control plane (docs/control_plane.md) must track:
  offered load swings around the mean, so any STATIC topology is wrong
  for part of the period.
- ``burst_arrivals``: on/off MMPP-style bursts — alternating
  exponentially-distributed ON (high-rate) and OFF (low-rate) phases,
  the adversarial shape for admission control and autoscaling
  (cold-start cost means a controller that chases every burst flaps).
"""

from __future__ import annotations

import math
import random
from dataclasses import dataclass, field
from typing import Optional, Sequence


@dataclass(frozen=True)
class Scenario:
    """One traffic class in the mix.

    ``shared_prefix_len`` > 0 models multi-turn / system-prompt reuse:
    every request of the scenario starts with the SAME token run (drawn
    once per scenario from the workload seed), so prefix caching and
    the radix index see realistic overlap.  Length bounds are inclusive
    uniform draws per request.

    ``prefix_group`` names the prefix draw to share: scenarios with
    the same group (and equal ``shared_prefix_len``) emit the SAME
    shared prefix — N tenant-pinned scenarios over one common system
    prompt, the cross-replica redundancy workload
    (``shared_prefix_catalog``).  None keeps the per-scenario-name
    draw, so existing catalogs generate exactly the traffic they
    always did.
    """

    name: str
    weight: float
    prompt_len: tuple[int, int]
    output_len: tuple[int, int]
    shared_prefix_len: int = 0
    stream: bool = False
    tenant: Optional[str] = None  # None -> the workload-level default
    # client priority/weight (x-omni-priority): None -> the neutral
    # weight (metrics/stats.py DEFAULT_PRIORITY), so catalogs that
    # never set it generate exactly the traffic they always did
    priority: Optional[int] = None
    prefix_group: Optional[str] = None


def default_catalog() -> list[Scenario]:
    """The mixed serving catalog the ROADMAP asks the curve to cover:
    chat, long-context, multi-turn shared-prefix, and streaming."""
    return [
        Scenario("chat", weight=0.5,
                 prompt_len=(32, 128), output_len=(16, 64)),
        Scenario("long_context", weight=0.2,
                 prompt_len=(512, 1024), output_len=(16, 32)),
        Scenario("multi_turn", weight=0.2,
                 prompt_len=(16, 64), output_len=(16, 32),
                 shared_prefix_len=256),
        Scenario("streaming", weight=0.1,
                 prompt_len=(32, 64), output_len=(32, 64), stream=True),
    ]


def shared_prefix_catalog(n_tenants: int = 4,
                          prefix_len: int = 64,
                          prompt_len: tuple[int, int] = (8, 24),
                          output_len: tuple[int, int] = (8, 16),
                          group: str = "system_prompt"
                          ) -> list[Scenario]:
    """The cache-economics workload (docs/load_testing.md): N equal-
    weight tenant-pinned scenarios all opening with ONE common system
    prompt (``prefix_group`` shares the draw).  Under a cache-blind
    router the common prefix lands on every replica — the redundancy
    the CacheEconomics board scores (tests/cache/test_cache_e2e.py)
    and prefix-affinity routing reclaims.  Seed-deterministic like every
    catalog: the prefix is drawn once from the workload seed."""
    if n_tenants < 1:
        raise ValueError("n_tenants must be positive")
    if prefix_len < 1:
        raise ValueError("prefix_len must be positive")
    return [
        Scenario(f"shared_prefix_t{i}", weight=1.0,
                 prompt_len=prompt_len, output_len=output_len,
                 shared_prefix_len=prefix_len,
                 tenant=f"tenant{i}", prefix_group=group)
        for i in range(n_tenants)
    ]


@dataclass
class LoadRequest:
    """One generated arrival: fire at ``at_s`` (offset from the run's
    t0), submit ``prompt_token_ids`` (or ``prompt`` text for HTTP
    drivers), collect up to ``max_tokens``."""

    at_s: float
    request_id: str
    scenario: str
    tenant: str
    prompt_token_ids: list[int] = field(default_factory=list)
    max_tokens: int = 16
    stream: bool = False
    # weighted-fair-queueing priority (None = neutral): run_inproc
    # stamps it into request metadata, run_http into x-omni-priority
    priority: Optional[int] = None

    @property
    def prompt(self) -> str:
        """Text form for HTTP drivers (the byte-tokenizer server path
        re-encodes it; exact token identity doesn't matter over HTTP,
        deterministic length does)."""
        return " ".join(f"tok{t}" for t in self.prompt_token_ids[:64])


def poisson_arrivals(rate_rps: float, num_requests: int,
                     seed: int = 0) -> list[float]:
    """``num_requests`` arrival offsets with exponential inter-arrival
    gaps at ``rate_rps`` (a Poisson process).  Seeded: same inputs,
    same schedule."""
    if rate_rps <= 0:
        raise ValueError(f"rate_rps must be > 0, got {rate_rps}")
    rng = random.Random(seed)
    t = 0.0
    out = []
    for _ in range(max(int(num_requests), 0)):
        t += rng.expovariate(rate_rps)
        out.append(t)
    return out


def trace_replay_arrivals(offsets: Sequence[float],
                          time_scale: float = 1.0) -> list[float]:
    """Replay explicit arrival offsets (seconds from t0), optionally
    compressed/stretched by ``time_scale`` (< 1 replays faster,
    sweeping offered load without editing the trace).  Offsets must be
    non-negative and sorted — a shuffled trace is almost always a
    units bug in the caller, not a workload."""
    if time_scale <= 0:
        raise ValueError(f"time_scale must be > 0, got {time_scale}")
    out = []
    prev = 0.0
    for i, off in enumerate(offsets):
        off = float(off)
        if off < 0 or off < prev:
            raise ValueError(
                f"trace offsets must be sorted and non-negative "
                f"(offset {i} = {off}, previous {prev})")
        prev = off
        out.append(off * time_scale)
    return out


def diurnal_arrivals(rate_rps: float, num_requests: int,
                     period_s: float = 60.0, amplitude: float = 0.8,
                     seed: int = 0, phase: float = 0.0) -> list[float]:
    """``num_requests`` offsets from a sinusoid-modulated Poisson
    process: instantaneous rate ``rate_rps * (1 + amplitude *
    sin(2*pi*t/period_s + phase))`` — a compressed diurnal cycle whose
    prefill:decode pressure mix shifts over the period.  Generated by
    Lewis-Shedler thinning against the peak rate, so the draws stay
    bit-deterministic per seed regardless of the modulation shape.
    ``amplitude`` in [0, 1): 0 degenerates to plain Poisson."""
    if rate_rps <= 0:
        raise ValueError(f"rate_rps must be > 0, got {rate_rps}")
    if not 0.0 <= amplitude < 1.0:
        raise ValueError(
            f"amplitude must be in [0, 1), got {amplitude}")
    if period_s <= 0:
        raise ValueError(f"period_s must be > 0, got {period_s}")
    rng = random.Random(seed)
    lam_max = rate_rps * (1.0 + amplitude)
    t = 0.0
    out: list[float] = []
    while len(out) < max(int(num_requests), 0):
        t += rng.expovariate(lam_max)
        lam_t = rate_rps * (1.0 + amplitude
                            * math.sin(2.0 * math.pi * t / period_s
                                       + phase))
        # thinning: accept with prob lambda(t)/lambda_max.  The draw
        # happens on EVERY candidate so the accept stream stays aligned
        # with the seed regardless of where the sinusoid sits
        if rng.random() * lam_max <= lam_t:
            out.append(t)
    return out


def burst_arrivals(base_rps: float, burst_rps: float,
                   num_requests: int, mean_on_s: float = 5.0,
                   mean_off_s: float = 15.0, seed: int = 0
                   ) -> list[float]:
    """``num_requests`` offsets from an on/off MMPP-style process:
    exponentially-distributed ON phases (mean ``mean_on_s``) arriving
    at ``burst_rps`` alternate with OFF phases (mean ``mean_off_s``)
    at ``base_rps`` — quiet background traffic punctured by bursts the
    controller must absorb without flapping.  ``base_rps`` may be 0
    (silent troughs).  Seeded and bit-deterministic."""
    if burst_rps <= 0:
        raise ValueError(f"burst_rps must be > 0, got {burst_rps}")
    if base_rps < 0:
        raise ValueError(f"base_rps must be >= 0, got {base_rps}")
    if mean_on_s <= 0 or mean_off_s <= 0:
        raise ValueError("mean_on_s and mean_off_s must be > 0")
    rng = random.Random(seed)
    out: list[float] = []
    t = 0.0          # current time
    on = False       # start in the OFF (background) phase
    phase_end = rng.expovariate(1.0 / mean_off_s)
    while len(out) < max(int(num_requests), 0):
        rate = burst_rps if on else base_rps
        if rate <= 0:
            # silent phase: jump to its end
            t = phase_end
            on = not on
            phase_end = t + rng.expovariate(
                1.0 / (mean_on_s if on else mean_off_s))
            continue
        gap = rng.expovariate(rate)
        if t + gap >= phase_end:
            # the next arrival would land past the phase boundary:
            # advance to the boundary and flip phase (memorylessness
            # makes discarding the partial gap distribution-correct)
            t = phase_end
            on = not on
            phase_end = t + rng.expovariate(
                1.0 / (mean_on_s if on else mean_off_s))
            continue
        t += gap
        out.append(t)
    return out


def build_workload(
    arrivals: Sequence[float],
    catalog: Optional[Sequence[Scenario]] = None,
    seed: int = 0,
    vocab_size: int = 32000,
    tenants: Sequence[str] = ("default",),
    id_prefix: str = "load",
    tenant_priorities: Optional[dict] = None,
) -> list[LoadRequest]:
    """Bind one scenario + concrete prompt/output draws to every
    arrival offset.  ``tenants`` round-robins across requests unless a
    scenario pins its own tenant.  ``tenant_priorities`` maps tenant ->
    WFQ priority (a scenario's own ``priority`` wins; unmapped tenants
    stay at the neutral weight).  Deterministic per (arrivals order,
    catalog, seed, vocab_size, tenants)."""
    catalog = list(catalog if catalog is not None else default_catalog())
    if not catalog:
        raise ValueError("catalog must not be empty")
    rng = random.Random(seed)
    weights = [max(s.weight, 0.0) for s in catalog]
    if sum(weights) <= 0:
        raise ValueError("catalog weights must sum > 0")
    # shared prefixes drawn ONCE per prefix key (the scenario's
    # prefix_group, or its name when ungrouped), before the
    # per-request stream, so adding requests never reshuffles them and
    # grouped scenarios share one draw in catalog order
    prefixes: dict[str, list[int]] = {}
    for s in catalog:
        if s.shared_prefix_len <= 0:
            continue
        k = s.prefix_group or s.name
        if k not in prefixes:
            prefixes[k] = [rng.randrange(1, vocab_size)
                           for _ in range(s.shared_prefix_len)]
    out: list[LoadRequest] = []
    for i, at_s in enumerate(arrivals):
        sc = rng.choices(catalog, weights=weights, k=1)[0]
        n_prompt = rng.randint(*sc.prompt_len)
        n_out = rng.randint(*sc.output_len)
        toks = list(prefixes.get(sc.prefix_group or sc.name, ())
                    if sc.shared_prefix_len > 0 else ())
        toks += [rng.randrange(1, vocab_size) for _ in range(n_prompt)]
        tenant = sc.tenant or tenants[i % len(tenants)]
        priority = sc.priority
        if priority is None and tenant_priorities:
            priority = tenant_priorities.get(tenant)
        out.append(LoadRequest(
            at_s=float(at_s),
            request_id=f"{id_prefix}-{i}",
            scenario=sc.name,
            tenant=tenant,
            prompt_token_ids=toks,
            max_tokens=n_out,
            stream=sc.stream,
            priority=priority,
        ))
    return out
