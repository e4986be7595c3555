"""Step-cache acceleration for DiT denoise loops (TeaCache analogue).

Reference: vllm_omni/diffusion/cache/ — ``CacheBackend`` ABC (base.py:31),
selector (selector.py:9), and the TeaCache hook skipping transformer
evaluations when the timestep-modulated input changed little
(teacache/hook.py:30, rel-L1 accumulation vs threshold).  The reference
reports 1.5-2.0x speedup at preserved quality
(docs/user_guide/diffusion_acceleration.md:15).

TPU-first mechanics: the reference installs Python forward-hooks that
branch per step — impossible under jit.  Here the skip decision is a
``lax.cond`` *inside* the compiled denoise loop: both branches are traced
once, the TPU executes only the taken branch at runtime, so skipped steps
genuinely save the DiT forward while the whole loop stays one XLA
computation.  State (last velocity, last input, accumulated rel-L1) rides
the ``fori_loop`` carry.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Callable, Optional

import jax
import jax.numpy as jnp


@dataclass(frozen=True)
class StepCacheConfig:
    # "teacache": input-drift gate skipping the WHOLE model eval
    # "taylorseer": like teacache, but skipped steps EXTRAPOLATE the
    #   velocity with a first/second-order Taylor step from finite
    #   differences of past computed evals instead of holding the last
    #   value (reference: cache-dit TaylorSeerCalibratorConfig,
    #   cache/cache_dit_backend.py:17)
    # "dbcache": dual-block cache (reference:
    #   diffusion/cache/cache_dit_backend.py DBCacheConfig) — the first
    #   ``fn_compute_blocks`` transformer blocks ALWAYS compute (a fresh
    #   anchor every step), their output drift gates reuse of a cached
    #   tail-contribution delta; higher quality than whole-model skipping
    #   because part of the network tracks every step
    backend: str = "teacache"     # "" disables
    rel_l1_threshold: float = 0.15
    # never skip the first/last steps (quality anchors, mirroring the
    # reference's warmup + final-step guards)
    warmup_steps: int = 1
    tail_steps: int = 1
    # dbcache: number of leading blocks always computed
    fn_compute_blocks: int = 4
    # taylorseer: extrapolation order (1 = linear, 2 = quadratic)
    taylor_order: int = 1
    # SCM (Step Computation Masking, reference cache-dit
    # scm_steps_mask, cache_dit_backend.py:46-55): a DETERMINISTIC
    # compute mask over step indices replacing the drift gate — entry i
    # True => step i computes, False => the cache serves it (warmup/
    # tail anchors still always compute).  None => dynamic drift gate.
    scm_steps_mask: "Optional[tuple]" = None

    @property
    def enabled(self) -> bool:
        return bool(self.backend)

    @staticmethod
    def from_dict(backend: str, d: dict) -> "StepCacheConfig":
        known = {k: v for k, v in (d or {}).items()
                 if k in StepCacheConfig.__dataclass_fields__ and k != "backend"}
        if "scm_steps_mask" in known and known["scm_steps_mask"] is not None:
            known["scm_steps_mask"] = tuple(
                bool(x) for x in known["scm_steps_mask"])
        return StepCacheConfig(backend=backend, **known)


def init_carry(latents: jax.Array):
    """(prev_velocity, prev_input, accumulated rel-L1) — accum starts at
    +inf so step 0 always computes."""
    return (
        jnp.zeros_like(latents),
        latents,
        jnp.asarray(jnp.inf, jnp.float32),
    )


def cached_eval(
    cache_cfg: StepCacheConfig,
    eval_fn: Callable[[jax.Array], jax.Array],
    latents: jax.Array,
    carry,
    i: jax.Array,
    num_steps: jax.Array,
    scm_mask=None,
):
    """Evaluate (or reuse) the velocity for this step.

    Returns (velocity, new_carry, skipped_flag).  ``eval_fn(latents)`` must
    be shape-preserving from latents to velocity.
    """
    prev_v, prev_lat, accum = carry
    diff = jnp.mean(jnp.abs(
        latents.astype(jnp.float32) - prev_lat.astype(jnp.float32)))
    base = jnp.mean(jnp.abs(prev_lat.astype(jnp.float32)))
    rel = diff / jnp.maximum(base, 1e-8)
    accum_new = accum + rel

    in_window = (i >= cache_cfg.warmup_steps) & (
        i < num_steps - cache_cfg.tail_steps
    )
    # a reusable velocity exists only after the first compute (accum is
    # +inf until then) — the SCM mask must not serve init_carry's zeros
    computed_once = jnp.isfinite(accum_new)
    if scm_mask is not None:
        skip = in_window & computed_once & ~scm_mask[i]
    else:
        skip = in_window & (accum_new < cache_cfg.rel_l1_threshold)

    def do_skip(_):
        # reuse the previous velocity; keep accumulating drift
        return prev_v, prev_lat, accum_new

    def do_compute(_):
        # match the carry dtype (CFG guidance math may promote to f32)
        v = eval_fn(latents).astype(prev_v.dtype)
        # reset the accumulator relative to this freshly-computed input
        return v, latents, jnp.asarray(0.0, jnp.float32)

    v, new_prev_lat, new_accum = jax.lax.cond(skip, do_skip, do_compute, None)
    return v, (v, new_prev_lat, new_accum), skip


def _scm_mask_array(cache_cfg: StepCacheConfig, sched_len: int):
    """Padded compute-mask [sched_len] from the config's tuple (True
    beyond the configured range so over-length schedules stay exact)."""
    import numpy as np

    m = np.ones((sched_len,), bool)
    mask = cache_cfg.scm_steps_mask
    n = min(len(mask), sched_len)
    m[:n] = np.asarray(mask[:n], bool)
    return jnp.asarray(m)


def taylor_init_carry(latents: jax.Array):
    """(v0, v1, v2, i0, i1, i2, prev_lat, accum): the last THREE
    computed velocities with their step indices (Newton
    divided-difference anchors, oldest first) plus the last computed
    input and the rel-L1 drift accumulator."""
    z = jnp.zeros_like(latents)
    return (z, z, z,
            jnp.asarray(-3, jnp.int32), jnp.asarray(-2, jnp.int32),
            jnp.asarray(-1, jnp.int32),
            latents, jnp.asarray(jnp.inf, jnp.float32))


def taylorseer_eval(
    cache_cfg: StepCacheConfig,
    eval_fn: Callable[[jax.Array], jax.Array],
    latents: jax.Array,
    carry,
    i: jax.Array,
    num_steps: jax.Array,
    scm_mask=None,
):
    """Evaluate, or Taylor-extrapolate, the velocity for this step.

    Skipped steps advance the last computed velocity along its Newton
    divided-difference derivative(s) through the last 2 (order 1) or 3
    (order 2) computed anchors instead of holding it — the calibrator
    idea of cache-dit's TaylorSeer.  Returns
    (velocity, new_carry, skipped_flag)."""
    v0, v1, v2, i0, i1, i2, prev_lat, accum = carry
    diff = jnp.mean(jnp.abs(
        latents.astype(jnp.float32) - prev_lat.astype(jnp.float32)))
    base = jnp.mean(jnp.abs(prev_lat.astype(jnp.float32)))
    rel = diff / jnp.maximum(base, 1e-8)
    accum_new = accum + rel

    in_window = (i >= cache_cfg.warmup_steps) & (
        i < num_steps - cache_cfg.tail_steps
    )
    # a valid derivative needs at least two computed anchors
    have_two = i1 >= 0
    if scm_mask is not None:
        skip = in_window & have_two & ~scm_mask[i]
    else:
        skip = in_window & have_two & (
            accum_new < cache_cfg.rel_l1_threshold)

    def do_skip(_):
        f = jnp.float32
        t, t1, t2 = i.astype(f), i1.astype(f), i2.astype(f)
        d21 = (v2.astype(f) - v1.astype(f)) / jnp.maximum(t2 - t1, 1.0)
        v = v2.astype(f) + d21 * (t - t2)
        if cache_cfg.taylor_order >= 2:
            t0 = i0.astype(f)
            have_three = (i0 >= 0).astype(f)
            d10 = (v1.astype(f) - v0.astype(f)) / jnp.maximum(
                t1 - t0, 1.0)
            d210 = (d21 - d10) / jnp.maximum(t2 - t0, 1.0)
            # Newton form through (t1, t2): + d2 * (t-t2)(t-t1)
            v = v + have_three * d210 * (t - t2) * (t - t1)
        return (v.astype(v2.dtype),
                (v0, v1, v2, i0, i1, i2, prev_lat, accum_new))

    def do_compute(_):
        v = eval_fn(latents).astype(v2.dtype)
        return (v, (v1, v2, v, i1, i2, i,
                    latents, jnp.asarray(0.0, jnp.float32)))

    v, new_carry = jax.lax.cond(skip, do_skip, do_compute, None)
    return v, new_carry, skip


def dbcache_init_carry(latents: jax.Array):
    """(prev_anchor_velocity, cached_tail_delta, accumulated rel-L1)."""
    return (
        jnp.zeros_like(latents),
        jnp.zeros_like(latents),
        jnp.asarray(jnp.inf, jnp.float32),
    )


def dbcache_eval(
    cache_cfg: StepCacheConfig,
    eval_first: Callable,   # (latents) -> (state, anchor_velocity)
    eval_rest: Callable,    # (state) -> full_velocity
    latents: jax.Array,
    carry,
    i: jax.Array,
    num_steps: jax.Array,
):
    """Dual-block cached velocity: the anchor (first Fn blocks + output
    head) computes EVERY step; when its drift since the last full compute
    stays under threshold, the cached tail delta (full - anchor) is
    reused instead of running the remaining blocks.

    Returns (velocity, new_carry, skipped_flag)."""
    prev_anchor, delta, accum = carry
    state, v_anchor = eval_first(latents)
    v_anchor = v_anchor.astype(prev_anchor.dtype)
    diff = jnp.mean(jnp.abs(
        v_anchor.astype(jnp.float32) - prev_anchor.astype(jnp.float32)))
    base = jnp.mean(jnp.abs(prev_anchor.astype(jnp.float32)))
    rel = diff / jnp.maximum(base, 1e-8)
    accum_new = accum + rel

    in_window = (i >= cache_cfg.warmup_steps) & (
        i < num_steps - cache_cfg.tail_steps
    )
    skip = in_window & (accum_new < cache_cfg.rel_l1_threshold)

    def do_skip(_):
        return v_anchor + delta, delta, accum_new

    def do_compute(_):
        v = eval_rest(state).astype(prev_anchor.dtype)
        return v, v - v_anchor, jnp.asarray(0.0, jnp.float32)

    v, new_delta, new_accum = jax.lax.cond(skip, do_skip, do_compute,
                                           None)
    return v, (v_anchor, new_delta, new_accum), skip


def init_cache_carry(cache_cfg, latents):
    """The cache backend's initial carry for ``latents``-shaped state —
    the host-visible half of the cross-chunk contract (chunked host
    loops thread this through ``run_denoise_loop(..., carry_in=...,
    return_carry=True)`` so skip state survives device-call
    boundaries)."""
    if cache_cfg is None or not cache_cfg.enabled:
        return None
    if cache_cfg.backend == "dbcache":
        return dbcache_init_carry(latents)
    if cache_cfg.backend == "taylorseer":
        return taylor_init_carry(latents)
    return init_carry(latents)


def run_denoise_loop(cache_cfg, schedule, eval_velocity, latents, num_steps,
                     solver: str = "euler", eval_split=None,
                     step_offset=None, total_steps=None, carry_in=None,
                     return_carry: bool = False):
    """Shared denoise fori_loop, optionally gated by the step cache.

    ``eval_velocity(latents, i)`` -> velocity (shape-preserving).  Returns
    ``(final_latents, skipped_count)`` — plus the cache carry when
    ``return_carry`` is set.  One implementation for every pipeline
    (image/video/audio) so cache-semantics changes land once.

    ``solver``: "euler" (FlowMatch Euler) or "unipc" (order-2 UniPC-style
    multistep, scheduler.multistep_step — fewer steps for the same
    quality; reference: scheduling_flow_unipc_multistep.py:741).

    Chunked host loops (``step_loop="host"``: K steps per device call
    on a schedule rolled to the chunk start) pass ``step_offset``
    (global index of local step 0), ``total_steps`` (the full run
    length, for the warmup/tail window), and thread the cache carry
    through ``carry_in``/``return_carry`` — the loop indexes the
    SCHEDULE locally and the CACHE globally, so skip decisions and
    Taylor anchors are identical to one uninterrupted loop.
    """
    from vllm_omni_tpu.diffusion import scheduler as fm

    if solver not in ("euler", "unipc"):
        raise ValueError(f"unknown solver {solver!r}")
    multistep = solver == "unipc"
    use_cache = cache_cfg is not None and cache_cfg.enabled
    use_dbcache = use_cache and cache_cfg.backend == "dbcache"
    use_taylor = use_cache and cache_cfg.backend == "taylorseer"
    offset = jnp.int32(0) if step_offset is None else step_offset
    total = num_steps if total_steps is None else total_steps
    scm_mask = None
    if use_cache and cache_cfg.scm_steps_mask is not None:
        scm_mask = _scm_mask_array(cache_cfg, int(schedule.sigmas.shape[0]))
    if use_dbcache and eval_split is None:
        raise ValueError(
            "dbcache needs the pipeline's split evaluation "
            "(eval_first, eval_rest) — this pipeline only supports "
            "teacache")
    if use_dbcache and scm_mask is not None:
        raise ValueError(
            "scm_steps_mask is not wired into the dbcache backend — "
            "use teacache or taylorseer for deterministic step masks")
    if multistep and (step_offset is not None or carry_in is not None):
        raise ValueError(
            "chunked denoise carries only the cache state — the unipc "
            "multistep solver state would be lost across chunks; use "
            "the euler solver")

    def ms_init(lat):
        return (jnp.zeros_like(lat, jnp.float32),
                jnp.asarray(0.0, jnp.float32))

    def advance(lat, v, i, ms):
        if multistep:
            new_lat, x0, lam = fm.multistep_step(
                schedule, lat, v, i, ms[0], ms[1])
            return new_lat, (x0, lam)
        return fm.step(schedule, lat, v, i), ms

    if use_cache:
        if use_dbcache:
            eval_first, eval_rest = eval_split

            def cache_eval(lat, i, ig, cc):
                return dbcache_eval(
                    cache_cfg, lambda l: eval_first(l, i), eval_rest,
                    lat, cc, ig, total)

        elif use_taylor:

            def cache_eval(lat, i, ig, cc):
                return taylorseer_eval(
                    cache_cfg, lambda l: eval_velocity(l, i), lat, cc,
                    ig, total, scm_mask=scm_mask)

        else:

            def cache_eval(lat, i, ig, cc):
                return cached_eval(
                    cache_cfg, lambda l: eval_velocity(l, i), lat, cc,
                    ig, total, scm_mask=scm_mask)

        default_carry = init_cache_carry(cache_cfg, latents)

        def body(i, carry):
            lat, cc, ms, skipped = carry
            v, cc, skip = cache_eval(lat, i, i + offset, cc)
            lat, ms = advance(lat, v, i, ms)
            return (lat, cc, ms, skipped + skip.astype(jnp.int32))

        lat, cc, _, skipped = jax.lax.fori_loop(
            0, num_steps, body,
            (latents, carry_in if carry_in is not None else default_carry,
             ms_init(latents), jnp.asarray(0, jnp.int32)),
        )
        if return_carry:
            return lat, skipped, cc
        return lat, skipped

    def body(i, carry):
        lat, ms = carry
        lat, ms = advance(lat, eval_velocity(lat, i), i, ms)
        return lat, ms

    lat, _ = jax.lax.fori_loop(
        0, num_steps, body, (latents, ms_init(latents)))
    if return_carry:
        return lat, jnp.asarray(0, jnp.int32), None
    return lat, jnp.asarray(0, jnp.int32)
