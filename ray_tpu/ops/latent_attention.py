"""Attention over a block-paged cache of LATENT rows (multi-head latent
attention in its absorbed form).

A cached token is one row ``[c (rank) | k_rope | 0 ..]`` shared by every head
(zeros up to whole lane tiles of 128: a TPU lays the pool out so anyway, and a
kernel may only copy whole tiles): the key of head ``i`` is ``[c W_uk,i |
k_rope]`` and its value ``c W_uv,i``. With
``W_uk`` folded into the query (``qa_i = q_nope_i W_uk,i^T``) and ``W_uv``
applied after the weighted sum, every head scores against the row itself and
sums the row's first ``rank`` numbers: the same numbers as the expanded form
(``models/latent_moe.py`` has both), with one row read a token, not ``2 * H``.

- ``latent_attention(q, pool, tables, lens, scale, rank)``: DECODE, one query
  token a slot. On a TPU where the rows tile (``_tiles``) the Pallas kernel
  ``latent_attend``, which reads each slot's LIVE blocks from the pool where
  they lie through two VMEM buffers and folds them into an online softmax;
  elsewhere ``reference_latent_attention``, the gather of the padded table and
  float32 einsums (CPU, shapes that do not tile, the tests' oracle). Decided
  from what the code can see, as ``ops/paged_attention.py`` decides.
- ``latent_chunk_attention(q, pool, tables, qpos, live, scale, rank)``:
  PREFILL, a tile of queries a slot, also absorbed: a tile walks its slot's
  table ``_KV_ROWS`` rows at a time up to the position of its last LIVE query
  (a dynamic trip count) under an online softmax, so neither the padded table
  nor the ``[queries, heads, keys]`` scores of a 9k prefix ever exist.
  ``live[t]`` of tile ``t``'s queries are real, the first ones; the work is
  done ``_QUERIES_PER_STEP`` queries at a time, and a group with no real
  query walks nothing and gives zeros (a tile nobody uses has ``live`` 0 and
  costs nothing). Products in the cache's dtype with float32 accumulation,
  softmax in float32. On a TPU where the rows tile the Pallas kernel
  ``latent_prefill_attend`` (a group x all heads a program, the scores never
  leave VMEM), else ``_plain_chunk_attention``, the same walk in plain
  ``jax.numpy`` (whose float32 scores go through HBM: at the served widths
  they, not the products, are its time). The expanded
  alternative (up-project each gathered step to per-head keys and values)
  costs ``2 * rank * H * (nope + v)`` operations a cached row a tile before any
  score; PERF.md has the forms timed.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops.attention import DEFAULT_MASK_VALUE, _use_pallas

# Rows folded into the softmax at once by the decode kernel: two buffers of
# rows x width and a float32 score tile [H, rows]. A step costs about a
# microsecond plus its rows' transfer (1,024 rows of 640 bf16 are 1.3 MB,
# 1.6 us at the chip's bandwidth), so a narrow step is latency and a wide one
# wastes the tail of short contexts.
_ROWS_PER_STEP = 1024
# Cached rows a prefill tile scores at once: the float32 scores of a tile of
# 256 tokens x 128 heads against 512 rows are 67 MB (the plain form's, in HBM).
_KV_ROWS = 512
# Queries one program of the prefill kernel holds, each with all its heads: 16
# x 128 heads are 2,048 rows, whose scores against 512 cached rows (4 MB in
# float32), sums (4 MB) and blocks of q and o stay inside 32 MB of VMEM. Each
# program reads its prefix again: 16 programs a tile of 256 read 16 x 11 MB at
# 8.5k rows, a fifth of a millisecond against the products' three.
_QUERIES_PER_STEP = 16


def _fold(q, rows, valid, carry, scale: float, rank: int):
    """One step of the online softmax, for the kernels and the plain walk
    alike: fold the cached ``rows`` [k, R] into the running (max, sum, weighted
    sum of the rows' first ``rank`` numbers) of the queries ``q`` [m, R], where
    ``valid`` [m, k]. A step's first row is valid for every query (the steps
    start at position 0), so the new maximum is a real score."""
    m_prev, l_prev, acc = carry
    s = jax.lax.dot_general(
        q, rows, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32) * scale
    s = jnp.where(valid, s, DEFAULT_MASK_VALUE)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    correction = jnp.exp(m_prev - m_new)
    l_new = l_prev * correction + jnp.sum(p, axis=-1, keepdims=True)
    acc = acc * correction + jax.lax.dot_general(
        p.astype(rows.dtype), rows[:, :rank], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    return m_new, l_new, acc


def _fold_start(m: int, rank: int):
    return (jnp.full((m, 1), -jnp.inf, jnp.float32), jnp.zeros((m, 1), jnp.float32),
            jnp.zeros((m, rank), jnp.float32))


def reference_latent_attention(q, pool, tables, lens, scale: float, rank: int):
    """q: [b, H, R] absorbed queries ``[qa | q_rope]``; pool: [P, bs, R];
    tables: [b, W] block ids; lens: [b] position of the token just written.
    → [b, H, rank] in ``q.dtype``. Scores, softmax and the sum in float32."""
    b, H, R = q.shape
    bs = pool.shape[1]
    m = tables.shape[1] * bs
    rows = pool[tables].reshape(b, m, R).astype(jnp.float32)
    scores = jnp.einsum("bhr,bmr->bhm", q.astype(jnp.float32), rows) * scale
    valid = jnp.arange(m)[None, :] <= lens[:, None]
    scores = jnp.where(valid[:, None, :], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhm,bmc->bhc", probs, rows[..., :rank]).astype(q.dtype)


def _latent_attend_kernel(
    tables_ref, lens_ref,  # scalar prefetch (SMEM): [b, W], [b]
    q_ref,  # VMEM [b, H, R]
    pool_hbm,  # the pool, left in HBM: [P, bs, R]
    o_ref,  # VMEM [b, H, rank]
    buf, sems,  # [2, C*bs, R], DMA semaphores [2]
    *, bs: int, chunk: int, rank: int, scale: float,
):
    """One program for all slots, as ``_paged_attend_kernel``: the work is the
    flat sequence of (slot, step) pairs, a step being ``chunk`` consecutive
    blocks of the slot's table; while one step is folded into the slot's
    softmax the next one's live blocks are in flight into the other buffer.
    One buffer is key and value both: every head scores against the whole
    row and sums its first ``rank`` numbers."""
    b, H, _ = q_ref.shape

    def last_token(slot):
        # ``lens`` past the table's end reads the whole table, as the plain
        # form's mask does; the host lets an idle slot's ``lens`` run on.
        return jnp.minimum(lens_ref[slot], tables_ref.shape[1] * bs - 1)

    def n_blocks(slot):
        return last_token(slot) // bs + 1

    def live_blocks(slot, step, which, act):
        """``act(copy)`` for each live block of the step."""
        def block(j, _):
            blk = tables_ref[slot, step * chunk + j]
            dst = pl.ds(pl.multiple_of(j * bs, bs), bs)
            act(pltpu.make_async_copy(pool_hbm.at[blk], buf.at[which, dst], sems.at[which]))

        jax.lax.fori_loop(0, jnp.minimum(n_blocks(slot) - step * chunk, chunk), block, None)

    def start(slot, step, which):
        live_blocks(slot, step, which, lambda c: c.start())

    def wait(slot, step, which):
        # DMA semaphores inside a kernel, not threading Events: no timeout exists
        live_blocks(slot, step, which, lambda c: c.wait())  # ray-tpu: lint-ignore[RTL008]

    # A step multiplies its whole buffer; rows past the live blocks hold
    # whatever an earlier step left, and 0 * NaN is NaN: start from zeros.
    buf[...] = jnp.zeros_like(buf)
    start(0, 0, 0)

    col = jax.lax.broadcasted_iota(jnp.int32, (H, chunk * bs), 1)

    def slot_body(slot, which):
        q = q_ref[slot]  # [H, R]
        length = last_token(slot)
        n_steps = pl.cdiv(n_blocks(slot), chunk)

        def step_body(step, carry):
            which, *softmax = carry
            last = step + 1 == n_steps
            nxt_slot = jnp.where(last, slot + 1, slot)

            @pl.when(nxt_slot < b)
            def _():
                start(nxt_slot, jnp.where(last, 0, step + 1), 1 - which)

            wait(slot, step, which)
            valid = step * (chunk * bs) + col <= length
            return (1 - which,) + _fold(q, buf[which], valid, softmax, scale, rank)

        which, _, l, acc = jax.lax.fori_loop(
            0, n_steps, step_body, (which,) + _fold_start(H, rank))
        o_ref[slot] = (acc / l).astype(o_ref.dtype)
        return which

    jax.lax.fori_loop(0, b, slot_body, 0)


def _latent_attend(q, pool, tables, lens, scale: float, rank: int, *, interpret: bool = False):
    b, H, R = q.shape
    P, bs, _ = pool.shape
    chunk = max(1, min(_ROWS_PER_STEP // bs, tables.shape[1]))
    return pl.pallas_call(
        functools.partial(_latent_attend_kernel, bs=bs, chunk=chunk, rank=rank, scale=scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(1,),
            in_specs=[
                pl.BlockSpec(memory_space=pltpu.VMEM),
                pl.BlockSpec(memory_space=pltpu.HBM),
            ],
            out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
            scratch_shapes=[pltpu.VMEM((2, chunk * bs, R), pool.dtype),
                            pltpu.SemaphoreType.DMA((2,))],
        ),
        out_shape=jax.ShapeDtypeStruct((b, H, rank), q.dtype),
        # q and o of every slot stay resident beside the two buffers.
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=64 * 1024 * 1024),
        interpret=interpret,
        name="latent_attend",
    )(tables, lens, q, pool)


def _tiles(pool, rank: int) -> bool:
    """Shapes the kernel's buffers and products tile on a TPU: the row and its
    value part whole lane tiles of 128, and a block whole sublane tiles of the
    cache's dtype (16 rows of bf16, 8 of float32)."""
    bs, width = pool.shape[1:]
    sublanes = 8 * 4 // jnp.dtype(pool.dtype).itemsize
    return width % 128 == 0 and rank % 128 == 0 and bs % sublanes == 0


@jax.named_scope("latent.attend")
def latent_attention(q, pool, tables, lens, scale: float, rank: int):
    """Decode: q [b, H, R] one absorbed query a slot; pool [P, bs, R] a flat
    pool of latent rows; tables [b, W]; lens [b] the position each slot's row
    was just written at → [b, H, rank]: per head the softmax-weighted sum of
    the rows' first ``rank`` numbers."""
    if _use_pallas() and _tiles(pool, rank):
        return _latent_attend(q, pool, tables, lens, scale, rank)
    return reference_latent_attention(q, pool, tables, lens, scale, rank)


def _latent_prefill_kernel(
    tables_ref, starts_ref, live_ref,  # scalar prefetch (SMEM): [n, steps * per], [n], [n]
    q_ref,  # VMEM [1, qb * H, R]: this program's queries, rows are (query, head)
    pool_hbm,  # the pool, left in HBM: [P, bs, R]
    o_ref,  # VMEM [1, qb * H, rank]
    buf, sems,  # [2, per * bs, R], DMA semaphores [2]
    *, bs: int, per: int, rank: int, scale: float, qb: int, heads: int,
):
    """Program ``g`` holds queries ``(g % nq) * qb ..`` of tile ``g // nq``, at
    consecutive positions from the tile's start, and walks the tile's table a
    step of ``per`` blocks at a time up to its own last query, the next step's
    blocks in flight into the other buffer while this one is folded in. Only
    the tile's first ``live_ref[t]`` queries are real: a program that holds
    one runs whole, one that holds none copies nothing, walks nothing and
    writes zeros."""
    nq = pl.num_programs(0) // starts_ref.shape[0]
    g = pl.program_id(0)
    t = g // nq
    at = (g % nq) * qb  # the program's first query, within its tile
    real = at < live_ref[t]  # the first is real, or none is
    kv = per * bs

    # Zeros, not whatever the block held: the rows go on through the layer, and
    # the next layer scatters what becomes of them into the pool, where both
    # kernels multiply masked probabilities of 0 into them (0 * NaN is NaN).
    @pl.when(jnp.logical_not(real))
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(real)
    def _():
        first = starts_ref[t] + at  # position of the program's first query
        n_steps = jnp.minimum((first + qb - 1) // kv + 1, tables_ref.shape[1] // per)

        def blocks(step, which, act):
            for j in range(per):
                blk = tables_ref[t, step * per + j]
                act(pltpu.make_async_copy(
                    pool_hbm.at[blk], buf.at[which, pl.ds(j * bs, bs)], sems.at[which]))

        blocks(0, 0, lambda c: c.start())
        q = q_ref[0]
        shape = (qb * heads, kv)
        row_pos = first + jax.lax.broadcasted_iota(jnp.int32, shape, 0) // heads
        col = jax.lax.broadcasted_iota(jnp.int32, shape, 1)

        def step_body(step, carry):
            which, *softmax = carry

            @pl.when(step + 1 < n_steps)
            def _():
                blocks(step + 1, 1 - which, lambda c: c.start())

            # DMA semaphores inside a kernel, not threading Events: no timeout exists
            blocks(step, which, lambda c: c.wait())  # ray-tpu: lint-ignore[RTL008]
            valid = step * kv + col <= row_pos
            return (1 - which,) + _fold(q, buf[which], valid, softmax, scale, rank)

        _, _, l, acc = jax.lax.fori_loop(
            0, n_steps, step_body, (0,) + _fold_start(qb * heads, rank))
        o_ref[0] = (acc / l).astype(o_ref.dtype)


def _latent_prefill_attend(q, pool, tables, starts, live, scale: float, rank: int, per: int, *,
                           interpret: bool = False):
    """q: [n, C, H, R]; tables: [n, steps * per] (padded); starts: [n] the
    position of each tile's first query, the others follow it one by one;
    live: [n] how many of each tile's queries are real."""
    n, C, H, R = q.shape
    bs = pool.shape[1]
    qb = _QUERIES_PER_STEP
    nq = C // qb
    out = pl.pallas_call(
        functools.partial(_latent_prefill_kernel, bs=bs, per=per, rank=rank, scale=scale,
                          qb=qb, heads=H),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(n * nq,),
            in_specs=[
                pl.BlockSpec((1, qb * H, R), lambda g, *_: (g, 0, 0)),
                pl.BlockSpec(memory_space=pltpu.HBM),
            ],
            out_specs=pl.BlockSpec((1, qb * H, rank), lambda g, *_: (g, 0, 0)),
            scratch_shapes=[pltpu.VMEM((2, per * bs, R), pool.dtype),
                            pltpu.SemaphoreType.DMA((2,))],
        ),
        out_shape=jax.ShapeDtypeStruct((n * nq, qb * H, rank), q.dtype),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=64 * 1024 * 1024),
        interpret=interpret,
        name="latent_prefill_attend",
    )(tables, starts, live, q.reshape(n * nq, qb * H, R), pool)
    return out.reshape(n, C, H, rank)


@jax.named_scope("latent.attend")
def latent_chunk_attention(q, pool, tables, qpos, live, scale: float, rank: int):
    """Prefill: q [n, C, H, R] absorbed queries by tile; pool [P, bs, R];
    tables [n, W] each tile's slot's block ids (the chunk's own rows are in
    the pool already); qpos [n, C] absolute positions, CONSECUTIVE within a
    tile; live [n] how many of a tile's queries, its first ones, are real. A
    query attends to the cached positions <= its own. → [n, C, H, rank] in
    ``q.dtype``: zeros from ``live`` rounded up to ``_QUERIES_PER_STEP`` on
    (the padded queries short of that are computed like real ones)."""
    C = q.shape[1]
    bs = pool.shape[1]
    W = tables.shape[1]
    per = max(1, min(_KV_ROWS // bs, W))  # blocks a step
    tables = jnp.pad(tables, ((0, 0), (0, -W % per)))  # the trash block, masked
    if _use_pallas() and _tiles(pool, rank) and C % _QUERIES_PER_STEP == 0:
        return _latent_prefill_attend(q, pool, tables, qpos[:, 0], live, scale, rank, per)
    return _plain_chunk_attention(q, pool, tables, qpos, live, scale, rank, per)


def _plain_chunk_attention(q, pool, tables, qpos, live, scale: float, rank: int, per: int):
    """``latent_chunk_attention`` in plain ``jax.numpy``; ``tables`` padded to
    whole steps of ``per`` blocks. The kernel's output, padding included: a
    tile's queries count in the kernel's groups of ``_QUERIES_PER_STEP``."""
    n, C, H, R = q.shape
    bs = pool.shape[1]
    kv = per * bs
    qb = _QUERIES_PER_STEP

    def tile(args):
        qt, row, pos, real = args  # [C, H, R], [steps * per], [C], []
        q2 = qt.reshape(C * H, R)
        pos2 = jnp.repeat(pos, H)[:, None]  # [C*H, 1]
        computed = jnp.minimum(-(-real // qb) * qb, C)  # whole groups
        keep = jnp.repeat(jnp.arange(C) < computed, H)[:, None]

        def step(j, carry):
            blocks = jax.lax.dynamic_slice_in_dim(row, j * per, per)
            valid = j * kv + jnp.arange(kv)[None, :] <= pos2
            return _fold(q2, pool[blocks].reshape(kv, R), valid, carry, scale, rank)

        # Up to the last computed query's position; no step where there is none.
        n_steps = jnp.where(computed > 0, (pos[0] + computed - 1) // kv + 1, 0)
        _, l, acc = jax.lax.fori_loop(0, n_steps, step, _fold_start(C * H, rank))
        # Past the computed rows zeros, and never 0 / 0: ``l`` is 0 where no
        # step ran, and short of its whole sum where the walk ended early.
        out = jnp.where(keep, acc / jnp.where(keep, l, 1.0), 0.0)
        return out.reshape(C, H, rank).astype(q.dtype)

    return jax.lax.map(tile, (q, tables, qpos, live))
