"""Mamba-2's sequence mixer: the state-space rule (SSD) in two forms that
must agree, and the convolution, activation and skip around it.

The rule, a head at a time, over a state ``S`` of ``[P, N]`` (head width x
state width) kept in float32::

    S_t = exp(a_t) * S_{t-1} + dt_t * u_t B_t^T      # a_t = -exp(A_log) dt_t
    y_t = S_t C_t

``B_t`` and ``C_t`` ``[N]`` are a GROUP's: head ``h`` of ``H`` reads those of
group ``h // (H / G)``.  With one group (Granite 4.0-H) they are ``[T, N]``
and every head's; with several (Nemotron-H: eight) ``[T, G, N]``.  ``u_t``
``[P]``, ``a_t <= 0`` and ``dt_t > 0`` are a head's.  (Reference: "Transformers
are SSMs", Dao and Gu 2024, and the ``mamba2`` mixers of the Granite 4.0-H
and Nemotron-H families' public configurations.)

A sequence's state lives in its slot of one pool ``[slots, H, P, N]`` a
layer (4 MB a sequence at the published 128 x 64 x 128), and that pool is
what a decode step moves: 128 rows touch all of its 537 MB.  So neither
form gathers the rows' states out of the pool and scatters them back (six
passes over it): **the pool is updated where it lies**, and comes back as
the second result, to be donated and rebound as the pages are.

``ssd_decode`` is the recurrence for one token a row: a Pallas kernel over
the rows' slot ids with the pool aliased from input to output, each live
row's state read once and written once (``_recurrent_kernel``); elsewhere
(the CPU's tests) the same numbers through a gather and a scatter.

``ssd_ragged`` serves a token-packed ragged batch (the layout of
ops/ragged_paged_attention.py: sequence ``i`` owns the rows ``cu_q_lens[i]
.. cu_q_lens[i] + q_lens[i]``).  A sequence with ONE token in the step (a
decode row of a mixed step: up to ``max_num_seqs`` of them beside a
prompt's chunk) takes the recurrent form through the same kernel and is
given no chunk.  The others take the chunked form, ``chunk`` tokens at a
time: with ``La_t`` the running sum of ``a`` inside the chunk,

    Y   = ((C B^T) o L) (dt o U) + exp(La) o (C S_0^T)
    S_Q = exp(La_Q) S_0 + sum_s exp(La_Q - La_s) dt_s u_s B_s^T

``L_ts = exp(La_t - La_s)`` for ``s <= t`` and 0 above: matrix products the
MXU takes.  A loop runs over the chunks that exist (its trip count is the
step's, not the bucket's: a pure-decode-plus-one-prompt step runs four
chunks, not ``T / chunk + S``), each reading its sequence's state from the
slot and writing it back; every exponent is a difference ``La_i - La_j``
with ``i >= j``, so nothing overflows.  ``chunk_slots`` counts the chunks a
step runs, from the host's copy of ``q_lens``: what ``ssd_chunk_fill``
observes.

Layouts: the pool ``[slots, H, P, N]`` float32; the convolution's state
``[rows, K - 1, C]`` and weight ``[K, C]`` as ops/gated_delta_rule.py has
them (shared with Gated DeltaNet at K = 4; here with a bias).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from vllm_omni_tpu.ops import gated_delta_rule as gdr
from vllm_omni_tpu.ops._dispatch import auto_use_pallas, interpret_flag

#: tokens a chunk of the chunked form holds (the published
#: ``mamba_chunk_size``)
CHUNK = 256
#: the chunked form's matrix products run at full float32 precision, as
#: Gated DeltaNet's do: they are a few per cent of a layer's arithmetic and
#: the state they feed is carried over thousands of tokens
PRECISION = jax.lax.Precision.HIGHEST
#: heads a grid step of the recurrent kernel takes: a block of the pool is
#: ``[HEAD_BLOCK, P, N]`` float32, 1 MB at the published 64 x 128, four of
#: them (in and out, double-buffered) in VMEM
HEAD_BLOCK = 32


# ----------------------------------------------------------------- oracle
def ssd_scan(u, dt, a, b, c, state):
    """The recurrence token by token over one sequence (u [T, H, P]; dt, a
    [T, H]; b, c [T, N], or [T, G, N] a group; state [H, P, N]): the plain
    form both served forms are tested against, never the served path."""
    def step(s, x):
        u_t, dt_t, a_t, b_t, c_t = x
        s = jnp.exp(a_t)[:, None, None] * s \
            + (dt_t[:, None] * u_t)[:, :, None] * b_t[:, None, :]
        return s, jnp.sum(s * c_t[:, None, :], axis=-1)

    f32 = jnp.float32
    b, c = _per_head(b, u.shape[1]), _per_head(c, u.shape[1])
    state, y = jax.lax.scan(
        step, state.astype(f32),
        tuple(x.astype(f32) for x in (u, dt, a, b, c)))
    return y, state


def _per_head(x, heads: int):
    """B or C a head: ``[R, N]`` (one group, every head's) or ``[R, G, N]``
    -> ``[R, H, N]``, head ``h`` reading group ``h // (H / G)``."""
    if x.ndim == 2:
        return jnp.broadcast_to(x[:, None, :],
                                (x.shape[0], heads, x.shape[-1]))
    return jnp.repeat(x, heads // x.shape[1], axis=1)


# ------------------------------------------------- one token a row, in place
def _recurrent_kernel(
    # scalar prefetch (SMEM)
    slots_ref,   # [R] the slot each grid row's block is (see _redirect)
    flags_ref,   # [R] bit 0: the row is live; bit 1: its sequence starts
    # inputs
    xdt_ref,     # [1, 1, P, hb]: dt * u, heads along the lanes
    dec_ref,     # [1, 1, P, hb]: exp(a), a head's value down its column
    b_ref,       # [1, 1, N]; with several groups [1, 1, gb, N]: the gb
    c_ref,       # groups whose heads the block holds, hb / gb heads each
    pool_ref,    # [1, hb, P, N]: the row's slot (aliased to pool_out)
    # outputs
    y_ref,       # [1, 1, P, hb]
    pool_out,    # [1, hb, P, N]
):
    r = pl.program_id(1)
    flags = flags_ref[r]
    live = jax.lax.rem(flags, 2) == 1

    @pl.when(live)
    def _():
        keep = jnp.where(flags >= 2, 0.0, 1.0)  # a new sequence: zeros
        hb = pool_ref.shape[1]
        if len(b_ref.shape) == 3:
            rows = [(b_ref[0], c_ref[0])]            # [1, N], every head's
        else:
            rows = [(b_ref[0, 0, g:g + 1], c_ref[0, 0, g:g + 1])
                    for g in range(b_ref.shape[2])]
        for h in range(hb):
            b_row, c_row = rows[h * len(rows) // hb]
            # a head's column [P, 1] broadcasts along the lanes, B and C's
            # row [1, N] down the sublanes
            s = (dec_ref[0, 0, :, h:h + 1] * keep) * pool_ref[0, h] \
                + xdt_ref[0, 0, :, h:h + 1] * b_row
            pool_out[0, h] = s
            y_ref[0, 0, :, h:h + 1] = jnp.sum(s * c_row, axis=-1,
                                              keepdims=True)

    # a row that is not live shares its block with the live row before it
    # (``_redirect``): the block is neither fetched again nor written back
    # until the index moves on, so its body must leave the buffers alone.
    # Only the first row of a head block has no live row before it: it
    # copies its block through, which the first live row then overwrites
    # (or, in a step with no live row at all, writes back unchanged)
    @pl.when(jnp.logical_and(jnp.logical_not(live), r == 0))
    def _():
        pool_out[...] = pool_ref[...]

    @pl.when(jnp.logical_not(live))
    def _():
        y_ref[...] = jnp.zeros_like(y_ref)


def _redirect(slots, live, num_slots: int):
    """The slot whose block each grid row names: a live row its own; a row
    that is not live (padding, or in a ragged step a sequence with another
    count of tokens than one) the live row's before it, so that consecutive
    grid steps name one block and nothing moves for it; the rows before the
    first live one, that one's."""
    r = slots.shape[0]
    at = jnp.where(live, jnp.arange(r, dtype=jnp.int32), -1)
    before = jax.lax.cummax(at, axis=0)
    src = jnp.where(before >= 0, before, jnp.argmax(live).astype(jnp.int32))
    return jnp.clip(slots[src], 0, num_slots - 1).astype(jnp.int32)


def _group_blocks(heads: int, head_block: int, groups: int
                  ) -> tuple[int, int]:
    """(groups a head block spans, head blocks a group spans) of ``groups``
    groups of B and C over ``heads`` heads taken ``head_block`` at a time:
    one of the two is 1, or the kernel is refused (``kernel_reason``).  B
    and C go in as ``[R, G / gb, gb, N]`` and a block of ``(1, 1, gb, N)``
    is whole in its last two axes, whatever ``gb``."""
    per_group = heads // groups
    return max(head_block // per_group, 1), max(per_group // head_block, 1)


def _vmem_limit(block_bytes: int) -> dict:
    """The kernel's VMEM where its four pool buffers (in and out, double-
    buffered) outgrow the compiler's default scope of 16 MB."""
    need = 4 * block_bytes + 4 * 2 ** 20
    return {"vmem_limit_bytes": need} if need > 14 * 2 ** 20 else {}


@functools.partial(jax.jit, static_argnames=("head_block",))
def _recurrent_inplace(u, dt, a, b, c, pool, slots, fresh, live,
                       head_block: int = HEAD_BLOCK):
    """The Pallas form of one token a row: grid (head blocks, rows), a
    block of the pool ``[head_block, P, N]`` found through the row's slot
    id, the pool aliased in and out.  With several groups of B and C a
    block is handed its own heads' groups (``_group_blocks``).  Returns
    (y [R, H, P] float32, the pool)."""
    f32 = jnp.float32
    r, h, p = u.shape
    n = b.shape[-1]
    hb = min(head_block, h)
    nb = h // hb

    def by_block(x):  # [R, H, P] -> [R, H / hb, P, hb]: heads on the lanes
        return jnp.transpose(x.astype(f32).reshape(r, nb, hb, p),
                             (0, 1, 3, 2))

    xdt = by_block(dt.astype(f32)[..., None] * u.astype(f32))
    dec = by_block(jnp.broadcast_to(jnp.exp(a.astype(f32))[..., None],
                                    (r, h, p)))
    flags = live.astype(jnp.int32) + 2 * fresh.astype(jnp.int32)
    row = pl.BlockSpec((1, 1, p, hb), lambda j, i, *_: (i, j, 0, 0))
    if b.ndim == 2:  # one group: every block reads the row's one B and C
        vec = pl.BlockSpec((1, 1, n), lambda j, i, *_: (i, 0, 0))

        def by_group(x):
            return x.astype(f32)[:, None, :]
    else:
        gb, span = _group_blocks(h, hb, b.shape[1])
        vec = pl.BlockSpec((1, 1, gb, n),
                           lambda j, i, *_: (i, j // span, 0, 0))

        def by_group(x):  # [R, G, N] -> [R, G / gb, gb, N]
            return x.astype(f32).reshape(r, -1, gb, n)
    slot = pl.BlockSpec((1, hb, p, n),
                        lambda j, i, slots_ref, _: (slots_ref[i], j, 0, 0))
    y, pool = pl.pallas_call(
        _recurrent_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(nb, r),
            in_specs=[row, row, vec, vec, slot],
            out_specs=[row, slot]),
        out_shape=[jax.ShapeDtypeStruct((r, nb, p, hb), f32),
                   jax.ShapeDtypeStruct(pool.shape, pool.dtype)],
        # operands count the two prefetched tables: the pool is the seventh
        input_output_aliases={6: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            **_vmem_limit(hb * p * n * 4)),
        interpret=interpret_flag(),
    )(_redirect(slots, live, pool.shape[0]), flags, xdt, dec,
      by_group(b), by_group(c), pool)
    return jnp.transpose(y, (0, 1, 3, 2)).reshape(r, h, p), pool


def _recurrent_gather(u, dt, a, b, c, pool, slots, fresh, live):
    """The same numbers through a gather of the rows' states and a scatter
    back (a copy of every touched state each way): the form of a platform
    that compiles no kernel."""
    f32 = jnp.float32
    s = pool.at[slots].get(mode="clip")
    s = jnp.where(fresh[:, None, None, None], 0.0, s)

    def a_head(x):  # B or C against the states: [R, 1 or H, 1, N]
        if x.ndim == 2:
            return x.astype(f32)[:, None, None, :]
        return _per_head(x.astype(f32), u.shape[1])[:, :, None, :]

    s = jnp.exp(a.astype(f32))[..., None, None] * s \
        + (dt.astype(f32)[..., None] * u.astype(f32))[..., None] * a_head(b)
    y = jnp.sum(s * a_head(c), axis=-1)
    at = jnp.where(live, slots, pool.shape[0])
    return (jnp.where(live[:, None, None], y, 0.0),
            pool.at[at].set(s.astype(pool.dtype), mode="drop"))


def kernel_reason(pool, groups: int = 1) -> str:
    """Why the recurrent kernel cannot take this pool ("" when it can): a
    block of it is whole float32 tiles with the state's width along the
    lanes, and holds whole groups of B and C or lies within one."""
    _, h, p, n = pool.shape
    if pool.dtype != jnp.float32:
        return f"the pool is {pool.dtype.name}, not float32"
    if n % 128 or p % 8:
        return f"a head's state [{p}, {n}] is no whole (8, 128) tiles"
    if h % min(HEAD_BLOCK, h):
        return f"{h} heads % {HEAD_BLOCK} != 0"
    per_group, hb = h // groups, min(HEAD_BLOCK, h)
    if h % groups or (hb % per_group and per_group % hb):
        return (f"{groups} groups of B and C over {h} heads do not tile "
                f"head blocks of {hb}")
    return ""


def ssd_decode(u, dt, a, b, c, pool, slots, fresh, live=None,
               use_pallas=None):
    """One token a row: u [R, H, P]; dt, a [R, H]; b, c [R, N], or [R, G,
    N] a group; ``pool`` [slots, H, P, N] float32; ``slots`` [R] each row's
    slot (past the end
    for a padding row), ``fresh`` [R] whether the row starts its sequence
    (its state is zeros, whatever the slot held), ``live`` [R] the rows to
    compute (None: those whose slot exists).  Returns (y [R, H, P] float32,
    zeros where the row is not live; the pool, each live row's slot
    updated, every other untouched)."""
    if live is None:
        live = slots < pool.shape[0]
    else:
        live = live & (slots < pool.shape[0])
    if use_pallas is None:
        use_pallas = auto_use_pallas(
            "ssd_decode",
            kernel_reason(pool, 1 if b.ndim == 2 else b.shape[1]))
    form = _recurrent_inplace if use_pallas else _recurrent_gather
    return form(u, dt, a, b, c, pool, slots, fresh, live)


# ------------------------------------------------------ the chunked form
def _chunk_plan(cu_q_lens, q_lens, slots, num_slots: int, t: int,
                chunk: int):
    """The chunks a step runs, as tables of ``t // chunk + S`` entries of
    which the first ``total`` are real: for each the sequence, the packed
    row its tokens start at and how many of them it holds.  Only a
    sequence of more than one token has chunks."""
    s = q_lens.shape[0]
    multi = (q_lens > 1) & (slots < num_slots)
    per_seq = jnp.where(multi, -(-q_lens // chunk), 0)
    ends = jnp.cumsum(per_seq)
    k = jnp.arange(t // chunk + s, dtype=jnp.int32)
    seq = jnp.clip(jnp.searchsorted(ends, k, side="right"), 0,
                   s - 1).astype(jnp.int32)
    first = (k - (ends[seq] - per_seq[seq])) * chunk   # offset in the seq
    return (ends[-1], seq, cu_q_lens[seq] + first,
            jnp.clip(q_lens[seq] - first, 0, chunk))


def _mm(spec, x, y):
    return jnp.einsum(spec, x, y, precision=PRECISION)


@functools.partial(jax.jit, static_argnames=("chunk", "use_pallas"))
def ssd_ragged(u, dt, a, b, c, pool, slots, fresh, cu_q_lens, q_lens,
               chunk: int = CHUNK, use_pallas=None):
    """A packed ragged batch: u [T, H, P]; dt, a [T, H]; b, c [T, N], or
    [T, G, N] a group; ``pool`` [slots, H, P, N]; ``slots`` and ``fresh``
    [S] (a sequence's
    slot, past the end where there is none; whether its context is this
    step's tokens alone); ``cu_q_lens`` [S + 1], ``q_lens`` [S].  Returns
    (y [T, H, P] float32, finite rows that nobody reads outside the
    segments; the pool with every sequence's slot at its state after its
    last token of the step)."""
    f32 = jnp.float32
    t = u.shape[0]
    num_slots = pool.shape[0]
    u, dt, a, b, c = (x.astype(f32) for x in (u, dt, a, b, c))
    # the one-token rows: the recurrent form, in place, no chunk
    first = jnp.clip(cu_q_lens[:-1], 0, t - 1)
    one = q_lens == 1
    y1, pool = ssd_decode(u[first], dt[first], a[first], b[first], c[first],
                          pool, slots, fresh, one, use_pallas=use_pallas)
    y = jnp.zeros(u.shape, f32).at[jnp.where(one, first, t)].set(
        y1, mode="drop")
    # the others, a chunk at a time over the chunks that exist
    q = min(chunk, t)
    total, seq_of, start_of, count_of = _chunk_plan(
        cu_q_lens, q_lens, slots, num_slots, t, q)
    idx = jnp.arange(q, dtype=jnp.int32)
    causal = idx[:, None] >= idx[None, :]
    # the three products that read B or C: without a group axis each is
    # every head's; with one (``g`` groups) a head reads its group's, so
    # the heads are taken a group at a time ([G, H / G]) on both sides
    h, g = u.shape[1], (0 if b.ndim == 2 else b.shape[1])

    def scores(c_w, b_w):      # C B^T against the decay [H, q, q]
        if not g:
            return _mm("tn,sn->ts", c_w, b_w)[None]
        return jnp.repeat(_mm("tgn,sgn->gts", c_w, b_w), h // g, axis=0)

    def read(c_w, s0):         # C S^T: [q, H, P]
        if not g:
            return _mm("tn,hpn->thp", c_w, s0)
        return _mm("tgn,gkpn->tgkp", c_w,
                   s0.reshape(g, h // g, *s0.shape[1:])).reshape(
            q, h, s0.shape[1])

    def write(to_end, b_w):    # sum_s x_s B_s^T: [H, P, N]
        if not g:
            return _mm("thp,tn->hpn", to_end, b_w)
        return _mm("tgkp,tgn->gkpn",
                   to_end.reshape(q, g, h // g, to_end.shape[-1]),
                   b_w).reshape(h, to_end.shape[-1], b_w.shape[-1])

    def one_chunk(carry):
        k, y, pool = carry
        i, start, count = seq_of[k], start_of[k], count_of[k]
        # a window of q packed rows that holds the chunk's: where the
        # chunk ends within q rows of the batch's end the window begins
        # before it, and the rows in front are masked like those behind
        lo = jnp.minimum(start, t - q)
        mine = (lo + idx >= start) & (lo + idx < start + count)

        def window(x):
            return jax.lax.dynamic_slice_in_dim(x, lo, q, axis=0)

        a_w = jnp.where(mine[:, None], window(a), 0.0)         # [q, H]
        xdt = jnp.where(mine[:, None], window(dt), 0.0)[..., None] \
            * window(u)                                        # [q, H, P]
        b_w, c_w = window(b), window(c)                # [q, N] a group
        la = jnp.cumsum(a_w, axis=0)                           # [q, H]
        decay = jnp.exp(jnp.where(
            causal[None], la.T[:, :, None] - la.T[:, None, :], -jnp.inf))
        within = _mm("hts,shp->thp", scores(c_w, b_w) * decay, xdt)
        slot = slots[i]
        s0 = jax.lax.dynamic_index_in_dim(pool, slot, keepdims=False)
        s0 = jnp.where(fresh[i] & (start == cu_q_lens[i]), 0.0, s0)
        carried = jnp.exp(la)[..., None] * read(c_w, s0)
        to_end = jnp.exp(la[-1][None, :] - la)[..., None] * xdt
        s1 = jnp.exp(la[-1])[:, None, None] * s0 + write(to_end, b_w)
        y = jax.lax.dynamic_update_slice_in_dim(
            y, jnp.where(mine[:, None, None], within + carried, window(y)),
            lo, axis=0)
        pool = jax.lax.dynamic_update_index_in_dim(
            pool, s1.astype(pool.dtype), slot, axis=0)
        return k + 1, y, pool

    _, y, pool = jax.lax.while_loop(
        lambda carry: carry[0] < total, one_chunk,
        (jnp.zeros((), jnp.int32), y, pool))
    return y, pool


def chunk_slots(q_lens, chunk: int = CHUNK) -> tuple[int, int]:
    """(tokens in chunks, chunk slots) of one ragged step, from the host's
    copy of its ``q_lens``: what ``ssd_ragged`` runs through the chunked
    form (the one-token rows, recurrent, are in neither count)."""
    lens = np.asarray(q_lens)
    lens = lens[lens > 1]
    return int(lens.sum()), int((-(-lens // chunk)).sum()) * chunk


# ----------------------------------------------------------- the two steps
def _split(xbc, dims, groups: int = 1):
    """silu(conv output) [T, H P + 2 G N] -> u [T, H, P], B and C [T, N]
    (one group) or [T, G, N]."""
    h, p, n = dims
    t = xbc.shape[0]
    u = xbc[:, :h * p].reshape(t, h, p)
    if groups == 1:
        return u, xbc[:, h * p:h * p + n], xbc[:, h * p + n:]
    gn = groups * n
    return (u, xbc[:, h * p:h * p + gn].reshape(t, groups, n),
            xbc[:, h * p + gn:].reshape(t, groups, n))


def mamba2_ragged(xbc, dt, a, conv_w, conv_b, d_skip, conv_state, pool,
                  slots, fresh, cu_q_lens, q_lens, dims, chunk: int = CHUNK,
                  groups: int = 1):
    """Convolution (with its bias), activation, the rule and the skip for
    a packed batch.  xbc [T, H P + 2 G N] (``groups`` G); dt, a [T, H]
    float32; conv_state
    [S, K - 1, C]: the sequences' carried inputs before the step (zeros
    where they start); ``pool``, ``slots``, ``fresh`` as ``ssd_ragged``
    takes them; ``dims`` = (H, P, N).  Returns (y [T, H, P] float32, the
    new conv state [S, K - 1, C], the pool)."""
    y, conv_state = gdr.causal_conv_ragged(
        xbc, conv_w, conv_state, cu_q_lens, q_lens, carried="gather")
    u, b, c = _split(jax.nn.silu(y + conv_b.astype(jnp.float32)), dims,
                     groups)
    y, pool = ssd_ragged(u, dt, a, b, c, pool, slots, fresh, cu_q_lens,
                         q_lens, chunk=chunk)
    return y + d_skip.astype(jnp.float32)[None, :, None] * u, conv_state, pool


def mamba2_decode(xbc, dt, a, conv_w, conv_b, d_skip, conv_state, pool,
                  slots, fresh, dims, groups: int = 1):
    """The same for one token a row (xbc [R, C], conv_state [R, K - 1,
    C])."""
    y, conv_state = gdr.causal_conv_update(xbc, conv_w, conv_state)
    u, b, c = _split(jax.nn.silu(y + conv_b.astype(jnp.float32)), dims,
                     groups)
    y, pool = ssd_decode(u, dt, a, b, c, pool, slots, fresh)
    return y + d_skip.astype(jnp.float32)[None, :, None] * u, conv_state, pool
