"""Decode attention over a block-paged KV cache: one query token per slot.

``paged_attention(q, ck, cv, tables, lens)`` attends slot ``i``'s token to
positions ``0 .. lens[i]`` (itself included) of the sequence whose blocks
``tables[i]`` names in the flat pool ``ck``/``cv`` ``[P, bs, KV, HD]``; the
softmax scale is ``HD**-0.5`` unless the model gives its own.

- ``reference_paged_attention``: gather every slot's whole table into a
  padded ``[b, W*bs, KV, HD]`` view and run masked float32 einsums over it.
  The plain form: CPU, shapes that do not tile, and the oracle of the tests.
- ``_paged_attend``: a Pallas kernel that reads each slot's LIVE blocks
  (``lens // bs + 1`` of them, not ``W``) from the pool where they lie,
  ``_BLOCKS_PER_STEP`` at a time through two VMEM buffers, and folds them
  into an online softmax. Nothing padded is written to HBM. The pool is
  passed as it is laid out; the kernel's own view of it, ``[P, bs*KV, HD]``,
  is a bitcast (one block is ``bs`` tiles of ``[KV, HD]`` either way).
- ``packed_paged_attention``: the same for heads narrower than a lane tile
  (``HD`` 64). Such a pool is declared ``[P, bs, KV*HD]``, a token's heads
  side by side on the lanes (as ``[.., KV, 64]`` a TPU would pad every head to
  128 lanes, in memory too), and goes through the SAME kernel as a pool of
  one wide head: each query head is laid into the lanes of its own kv head
  with zeros elsewhere, so the one product scores it against its own keys
  alone, and of the weighted sum of whole rows it keeps its own lanes.

Which one runs is decided from what the code can see (``_tiles``), as
``ops/attention.py`` decides for the flash kernels.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops.attention import DEFAULT_MASK_VALUE, _use_pallas

# Blocks folded into the softmax at once. A step costs about a microsecond
# of latency plus 0.07 us a block of its width, live or not, so a wide step
# suits long contexts and a narrow one idle slots. Alone on a v5e at the
# serve cell's shapes, 32 slots, wall-clock ms a call (dispatch included) at
# 4 / 8 / 16 / 32 blocks: 16 slots of ~400 tokens 0.104 / 0.090 / 0.095 /
# 0.119; 32 of ~1,000 tokens 0.347 / 0.257 / 0.233 / 0.229; the plain form
# 0.36-0.40 whatever is live. By the device's trace at 16: 23 us all idle,
# 167 us at 31 slots of 58 blocks (705 GB/s of live K and V).
_BLOCKS_PER_STEP = 16
# ... and at most this many (token, kv head) rows, which bounds the buffers
# (four of rows x HD) and the score tile ([H, rows] float32) whatever the
# block's shape; 16 blocks of the serve cell's are 2,048.
_ROWS_PER_STEP = 2048


def reference_paged_attention(q, ck, cv, tables, lens, scale=None):
    """q: [b, H, HD]; ck/cv: [P, bs, KV, HD]; tables: [b, W] block ids into
    the pool; lens: [b] position of the token just written. → [b, H*HD] in
    ``q.dtype``. Scores, softmax and the weighted sum in float32."""
    b, H, HD = q.shape
    scale = HD**-0.5 if scale is None else scale
    bs, KV = ck.shape[1:3]
    m = tables.shape[1] * bs
    qg = q.reshape(b, KV, H // KV, HD).astype(jnp.float32)
    ck_g = ck[tables].reshape(b, m, KV, HD).astype(jnp.float32)
    cv_g = cv[tables].reshape(b, m, KV, HD).astype(jnp.float32)
    scores = jnp.einsum("bkgd,bmkd->bkgm", qg, ck_g) * scale
    valid = jnp.arange(m)[None, :] <= lens[:, None]  # [b, m]
    scores = jnp.where(valid[:, None, None, :], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    og = jnp.einsum("bkgm,bmkd->bkgd", probs, cv_g)
    return og.reshape(b, H * HD).astype(q.dtype)


def _paged_attend_kernel(
    tables_ref, lens_ref,  # scalar prefetch (SMEM): [b, W], [b]
    q_ref,  # VMEM [b, H, HD]
    k_hbm, v_hbm,  # the pools, left in HBM: [P, bs*KV, HD]
    o_ref,  # VMEM [b, H, HD]
    k_buf, v_buf, sems,  # [2, C*bs*KV, HD] x2, DMA semaphores [2, 2]
    *, bs: int, kv_heads: int, chunk: int, scale: float,
):
    """One program for all slots. The work is the flat sequence of (slot,
    step) pairs, a step being ``chunk`` consecutive blocks of the slot's
    table; while one step is folded into the slot's softmax the next one's
    live blocks (the next slot's first, at a slot's end) are in flight
    into the other buffer. Rows of a buffer are (token, kv head) pairs, so
    ONE product scores every q head against every kv head's keys and the
    mask keeps a head's own: KV times the MXU work the algorithm needs, on
    an MXU that decode leaves idle, for no relayout of K."""
    b, H, HD = q_ref.shape
    rows = bs * kv_heads  # of one block
    group = H // kv_heads

    def last_token(slot):
        # ``lens`` past the table's end reads the whole table, as the plain
        # form's mask does; the host lets an idle slot's ``lens`` run on.
        return jnp.minimum(lens_ref[slot], tables_ref.shape[1] * bs - 1)

    def n_blocks(slot):
        return last_token(slot) // bs + 1

    def live_blocks(slot, step, buf, act):
        """``act(k_copy, v_copy)`` for each live block of the step."""
        def block(j, _):
            blk = tables_ref[slot, step * chunk + j]
            dst = pl.ds(pl.multiple_of(j * rows, rows), rows)
            act(
                pltpu.make_async_copy(k_hbm.at[blk], k_buf.at[buf, dst], sems.at[0, buf]),
                pltpu.make_async_copy(v_hbm.at[blk], v_buf.at[buf, dst], sems.at[1, buf]),
            )

        jax.lax.fori_loop(0, jnp.minimum(n_blocks(slot) - step * chunk, chunk), block, None)

    def start(slot, step, buf):
        live_blocks(slot, step, buf, lambda k, v: (k.start(), v.start()))

    def wait(slot, step, buf):
        # DMA semaphores inside a kernel, not threading Events: no timeout exists
        live_blocks(slot, step, buf, lambda k, v: (k.wait(), v.wait()))  # ray-tpu: lint-ignore[RTL008]

    # A step multiplies its whole buffer; rows past the live blocks hold
    # whatever an earlier step left, and 0 * NaN is NaN: start from zeros.
    v_buf[...] = jnp.zeros_like(v_buf)
    start(0, 0, 0)

    # Column c of a score tile is (token c // KV of the step, kv head c % KV).
    shape = (H, chunk * rows)
    col = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    own_head = jax.lax.broadcasted_iota(jnp.int32, shape, 0) // group == col % kv_heads
    col_token = col // kv_heads

    def slot_body(slot, buf):
        q = q_ref[slot]  # [H, HD]
        length = last_token(slot)
        n_steps = pl.cdiv(n_blocks(slot), chunk)

        def step_body(step, carry):
            buf, m_prev, l_prev, acc = carry
            last = step + 1 == n_steps
            nxt_slot = jnp.where(last, slot + 1, slot)

            @pl.when(nxt_slot < b)
            def _():
                start(nxt_slot, jnp.where(last, 0, step + 1), 1 - buf)

            wait(slot, step, buf)
            s = jax.lax.dot_general(
                q, k_buf[buf], (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
            ) * scale  # [H, C*rows]
            valid = own_head & (step * (chunk * bs) + col_token <= length)
            s = jnp.where(valid, s, DEFAULT_MASK_VALUE)
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            p = jnp.exp(s - m_new)  # a step's first token is live: m_new is a real score
            correction = jnp.exp(m_prev - m_new)
            l_new = l_prev * correction + jnp.sum(p, axis=-1, keepdims=True)
            v = v_buf[buf]
            acc = acc * correction + jax.lax.dot_general(
                p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            return 1 - buf, m_new, l_new, acc

        buf, _, l, acc = jax.lax.fori_loop(0, n_steps, step_body, (
            buf,
            jnp.full((H, 1), -jnp.inf, jnp.float32),
            jnp.zeros((H, 1), jnp.float32),
            jnp.zeros((H, HD), jnp.float32),
        ))
        o_ref[slot] = (acc / l).astype(o_ref.dtype)
        return buf

    jax.lax.fori_loop(0, b, slot_body, 0)


def _paged_attend(q, ck, cv, tables, lens, scale=None, *, interpret: bool = False):
    """q: [b, H, HD]; ck/cv: [P, bs, KV, HD] → [b, H*HD]."""
    b, H, HD = q.shape
    P, bs, KV, _ = ck.shape
    rows = bs * KV
    scale = HD**-0.5 if scale is None else scale
    chunk = max(1, min(_BLOCKS_PER_STEP, _ROWS_PER_STEP // rows, tables.shape[1]))
    buf = pltpu.VMEM((2, chunk * rows, HD), ck.dtype)
    out = pl.pallas_call(
        functools.partial(_paged_attend_kernel, bs=bs, kv_heads=KV, chunk=chunk, scale=scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(1,),
            in_specs=[
                pl.BlockSpec(memory_space=pltpu.VMEM),
                pl.BlockSpec(memory_space=pltpu.HBM),
                pl.BlockSpec(memory_space=pltpu.HBM),
            ],
            out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
            scratch_shapes=[buf, buf, pltpu.SemaphoreType.DMA((2, 2))],
        ),
        out_shape=jax.ShapeDtypeStruct((b, H, HD), q.dtype),
        interpret=interpret,
        name="paged_attend",
    )(tables, lens, q, ck.reshape(P, rows, HD), cv.reshape(P, rows, HD))
    return out.reshape(b, H * HD)


def _tiles(ck) -> bool:
    """Shapes the kernel's buffers and products tile on a TPU: rows whose
    width (``HD``; ``KV*HD`` of a packed pool, which comes here as one wide
    head) is whole lane tiles of 128, and a block whose (token, kv head) rows
    fill whole sublane tiles of the cache's dtype (16 rows of bf16, 8 of
    float32)."""
    bs, KV, HD = ck.shape[1:]
    sublanes = 8 * 4 // jnp.dtype(ck.dtype).itemsize
    return HD % 128 == 0 and (bs * KV) % sublanes == 0


@jax.named_scope("paged.attend")
def paged_attention(q, ck, cv, tables, lens, scale=None):
    """q: [b, H, HD] one token a slot; ck/cv: [P, bs, KV, HD] a flat pool;
    tables: [b, W] the slots' block ids in it; lens: [b] the position each
    slot's token was just written at; scale: of the scores (``HD**-0.5``
    unless given) → [b, H*HD] in ``q.dtype``. The kernel on a TPU where the
    shapes tile, else the plain form."""
    if _use_pallas() and _tiles(ck):
        return _paged_attend(q, ck, cv, tables, lens, scale)
    return reference_paged_attention(q, ck, cv, tables, lens, scale)


@jax.named_scope("paged.attend")
def packed_paged_attention(q, ck, cv, tables, lens, scale, *, interpret: bool = False):
    """q: [b, H, HD]; ck/cv: [P, bs, KV*HD], a token's kv heads side by side
    (module docstring) → [b, H*HD] in ``q.dtype``."""
    b, H, HD = q.shape
    P, bs, row = ck.shape
    KV = row // HD
    if not interpret and not (_use_pallas() and _tiles(ck[:, :, None])):
        split = (P, bs, KV, HD)
        return reference_paged_attention(q, ck.reshape(split), cv.reshape(split), tables, lens, scale)
    # Head h's query in the lanes of kv head h // group, zeros in the others'.
    own = jnp.arange(H)[:, None] // (H // KV) == jnp.arange(KV)[None, :]  # [H, KV]
    wide = jnp.where(own[None, :, :, None], q[:, :, None, :], 0).reshape(b, H, row)
    out = _paged_attend(wide, ck[:, :, None], cv[:, :, None], tables, lens, scale,
                        interpret=interpret).reshape(b, H, KV, HD)
    # Of the sum over whole rows, a head's own lanes: the other KV - 1 parts
    # are sums of other heads' values under this head's weights.
    return jnp.sum(jnp.where(own[None, :, :, None], out, 0), axis=2).reshape(b, H * HD)
