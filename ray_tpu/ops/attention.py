"""Attention ops: Pallas flash-attention forward for TPU + reference path.

The reference framework has no attention kernels (it orchestrates external
libraries); on TPU the kernel must be native (SURVEY.md §2.9). Design:

- ``flash_attention``: blocked online-softmax forward as a Pallas kernel
  (MXU-shaped 128-tiles, fp32 accumulation) that also emits the per-row
  logsumexp, with a custom VJP running the flash *backward* as two Pallas
  kernels (dQ over q-blocks; dK/dV over k-blocks) — memory stays
  O(seq·d), no seq² materialization in either direction.
  SEQUENCE CEILING: that O(seq·d) is VMEM, not HBM. Each program keeps
  one head's whole K and V (forward, dQ) or whole Q and dO (dK/dV)
  resident, so seq·head_dim·itemsize per array is bounded by
  ``_resident_limit_bytes`` — 10,240 tokens at head_dim 128 in bf16,
  5,120 in fp32 — and a longer sequence raises ValueError before
  lowering. Longer sequences split over chips (``MeshPlan(sp=...)``)
  until the kernels tile K/V. The backward additionally needs a q
  length that is a multiple of 128 (forward-only callers — the serving
  prefill buckets 8..72 — do not).
- ``reference_attention``: straight jnp implementation used for CPU tests,
  as the non-TPU VJP path, and as the numerical oracle.

Layouts: q is [batch, q_heads, seq, head_dim]; k/v are
[batch, kv_heads, seq, head_dim] with q_heads % kv_heads == 0 — GQA is
NATIVE: the kernels index the shared kv head per q-head group instead of
the caller repeating K/V, so a Mistral-style 8-kv-head config reads each
K/V head once from HBM (and never materializes the repeated tensors the
old caller-side repeat cost both HBM and VJP traffic for).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_MASK_VALUE = -0.7 * float(jnp.finfo(jnp.float32).max)


def reference_attention(q, k, v, causal: bool = True, scale: Optional[float] = None):
    *_, q_len, head_dim = q.shape
    if k.shape[1] != q.shape[1]:  # GQA: expand kv heads for the oracle
        rep = q.shape[1] // k.shape[1]
        k = jnp.repeat(k, rep, axis=1)
        v = jnp.repeat(v, rep, axis=1)
    k_len = k.shape[-2]
    scale = scale if scale is not None else head_dim**-0.5
    logits = jnp.einsum("bhqd,bhkd->bhqk", q, k, preferred_element_type=jnp.float32)
    logits = logits * scale
    if causal:
        mask = jnp.tril(jnp.ones((q_len, k_len), dtype=bool), k=k_len - q_len)
        logits = jnp.where(mask, logits, DEFAULT_MASK_VALUE)
    probs = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", probs.astype(v.dtype), v)


# ---------------------------------------------------------------------------
# Pallas forward kernel
# ---------------------------------------------------------------------------


def _flash_fwd_kernel(
    q_ref, k_ref, v_ref, o_ref, lse_ref, *, block_k: int, causal: bool, scale: float,
    k_len_actual: int
):
    """One (batch·head, q-block) program: online softmax over k blocks.

    ``k_ref`` is padded to a multiple of ``block_k`` by the wrapper so
    dynamic k-block slices never clamp (a clamped slice would silently
    shift key rows); padded columns are masked via ``k_len_actual``.
    """
    q = q_ref[0].astype(jnp.float32) * scale  # [block_q, d]
    block_q, head_dim = q.shape
    k_len = k_ref.shape[1]  # padded length, multiple of block_k
    q_blk = pl.program_id(1)
    q_start = q_blk * block_q

    num_k_blocks = k_len // block_k
    if causal:
        # Only k blocks at or before the diagonal contribute. Clamp: with
        # block_q > block_k a partial final q-block would otherwise
        # overshoot and issue a clamped (row-shifting) slice.
        num_k_blocks_needed = jnp.minimum(
            jax.lax.div(q_start + block_q - 1, block_k) + 1, num_k_blocks
        )
    else:
        num_k_blocks_needed = num_k_blocks

    def make_body(masked: bool):
        def body(kb, carry):
            acc, m_prev, l_prev = carry
            k_start = kb * block_k
            kblk = k_ref[0, pl.ds(k_start, block_k), :].astype(jnp.float32)
            vblk = v_ref[0, pl.ds(k_start, block_k), :].astype(jnp.float32)
            s = jax.lax.dot_general(
                q, kblk, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
            )  # [block_q, block_k]
            needs_pad_mask = k_len_actual < k_len
            if masked and (causal or needs_pad_mask):
                k_ids = k_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
                valid = (k_ids < k_len_actual) if needs_pad_mask else True
                if causal:
                    q_ids = q_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
                    valid = valid & (q_ids >= k_ids)
                s = jnp.where(valid, s, DEFAULT_MASK_VALUE)
            m_cur = jnp.max(s, axis=-1)
            m_new = jnp.maximum(m_prev, m_cur)
            p = jnp.exp(s - m_new[:, None])
            correction = jnp.exp(m_prev - m_new)
            l_new = l_prev * correction + jnp.sum(p, axis=-1)
            acc = acc * correction[:, None] + jax.lax.dot_general(
                p, vblk, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
            )
            return acc, m_new, l_new

        return body

    init = (
        jnp.zeros((block_q, head_dim), jnp.float32),
        jnp.full((block_q,), -jnp.inf, jnp.float32),
        jnp.zeros((block_q,), jnp.float32),
    )
    if causal:
        # Two phases: k blocks fully below the diagonal need no mask (the
        # mask's iota/compare/select is VPU work comparable to the MXU
        # matmul at these block shapes); only diagonal-crossing blocks pay
        # for it. The clamp to whole real-K blocks keeps the unmasked
        # phase off the zero padding AND in-bounds when q_len > k_len
        # (self-attention never hits either, cross-length causal does).
        num_full = jnp.minimum(
            jax.lax.div(q_start, block_k), k_len_actual // block_k
        )
        carry = jax.lax.fori_loop(0, num_full, make_body(False), init)
        acc, m, l = jax.lax.fori_loop(
            num_full, num_k_blocks_needed, make_body(True), carry
        )
    else:
        acc, m, l = jax.lax.fori_loop(0, num_k_blocks_needed, make_body(True), init)
    o_ref[0] = (acc / jnp.maximum(l, 1e-30)[:, None]).astype(o_ref.dtype)
    # logsumexp of the scaled scores — the backward kernels rebuild
    # P = exp(S - lse) from it instead of re-running the softmax.
    lse_ref[0, 0] = m + jnp.log(jnp.maximum(l, 1e-30))


def _kv_index_map(q_heads: int, kv_heads: int):
    """Program id over batch·q_heads → the [batch·kv_heads] row holding
    that q head's shared K/V (the GQA mapping; identity when MHA)."""
    group = q_heads // kv_heads

    def imap(b, i):
        return ((b // q_heads) * kv_heads + (b % q_heads) // group, 0, 0)

    return imap


def _resident_limit_bytes(head_dim: int) -> int:
    """Largest seq·head_dim·itemsize one resident array may have, from
    AOT compiles against a v5e topology (16 MiB scoped VMEM per core;
    the resident arrays are double-buffered and share it with the q/o
    blocks and the fp32 score temporaries). head_dim 128: bf16 12,288
    and fp32 5,120 rows compile, 13,312 and 6,144 are refused; head_dim
    256 bf16: 6,144 compiles, 7,168 is refused; head_dim 64: bf16
    49,152 and fp32 24,576 compile, 65,536 and 32,768 are refused."""
    return (6 << 20) if head_dim <= 64 else (5 << 19)


def _check_resident_fits(what: str, rows: int, head_dim: int, dtype) -> None:
    itemsize = jnp.dtype(dtype).itemsize
    limit = _resident_limit_bytes(head_dim)
    if rows * head_dim * itemsize > limit:
        raise ValueError(
            f"flash_attention: {what} of {rows} rows at head_dim {head_dim} "
            f"({jnp.dtype(dtype).name}) exceeds the kernels' sequence ceiling "
            f"of {limit // (head_dim * itemsize)} rows: each program keeps one "
            f"head's whole K/V (forward, dQ) and Q/dO (dK/dV) resident in "
            f"VMEM, at most {limit / 2**20:.1f} MiB per array on a v5e core. "
            f"Split the sequence over chips (MeshPlan sp > 1) or shorten it."
        )


def _flash_forward(q, k, v, causal: bool, scale: float, block_q: int, block_k: int, interpret: bool):
    batch, heads, q_len, head_dim = q.shape
    kv_heads = k.shape[1]
    assert heads % kv_heads == 0, (heads, kv_heads)
    k_len = k.shape[2]
    bq = min(block_q, q_len)
    bk = min(block_k, k_len)
    qr = q.reshape(batch * heads, q_len, head_dim)
    kr = k.reshape(batch * kv_heads, k_len, head_dim)
    vr = v.reshape(batch * kv_heads, k_len, head_dim)
    # Pad K/V so every k-block slice is in bounds (see kernel docstring).
    k_pad = (-k_len) % bk
    if k_pad:
        kr = jnp.pad(kr, ((0, 0), (0, k_pad), (0, 0)))
        vr = jnp.pad(vr, ((0, 0), (0, k_pad), (0, 0)))
    k_len_padded = k_len + k_pad
    if not interpret:
        _check_resident_fits("K/V", k_len_padded, head_dim, k.dtype)
    kv_map = _kv_index_map(heads, kv_heads)
    grid = (batch * heads, pl.cdiv(q_len, bq))
    out, lse = pl.pallas_call(
        functools.partial(
            _flash_fwd_kernel, block_k=bk, causal=causal, scale=scale, k_len_actual=k_len
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, head_dim), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, k_len_padded, head_dim), kv_map),
            pl.BlockSpec((1, k_len_padded, head_dim), kv_map),
        ],
        out_specs=[
            pl.BlockSpec((1, bq, head_dim), lambda b, i: (b, i, 0)),
            # [bh, 1, q_len] with a unit middle dim keeps the (8,128) TPU
            # tile constraint satisfied: block dims (1, bq).
            pl.BlockSpec((1, 1, bq), lambda b, i: (b, 0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((batch * heads, q_len, head_dim), q.dtype),
            jax.ShapeDtypeStruct((batch * heads, 1, q_len), jnp.float32),
        ],
        interpret=interpret,
        name="flash_fwd",
    )(qr, kr, vr)
    return (
        out.reshape(batch, heads, q_len, head_dim),
        lse.reshape(batch, heads, q_len),
    )


# ---------------------------------------------------------------------------
# Pallas backward kernels
# ---------------------------------------------------------------------------


def _flash_bwd_dq_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, *,
    block_k: int, causal: bool, scale: float, k_len_actual: int
):
    """One (batch·head, q-block) program: dQ = scale · Σ_k dS·K over k
    blocks, with dS = P ∘ (dO·Vᵀ − Δ) and P rebuilt from the saved lse."""
    q = q_ref[0].astype(jnp.float32)
    do = do_ref[0].astype(jnp.float32)
    lse = lse_ref[0, 0].astype(jnp.float32)
    delta = delta_ref[0, 0].astype(jnp.float32)
    block_q, head_dim = q.shape
    k_len = k_ref.shape[1]
    q_start = pl.program_id(1) * block_q
    num_k_blocks = k_len // block_k
    if causal:
        num_k_blocks_needed = jnp.minimum(
            jax.lax.div(q_start + block_q - 1, block_k) + 1, num_k_blocks
        )
    else:
        num_k_blocks_needed = num_k_blocks

    def make_body(masked: bool):
        def body(kb, acc):
            k_start = kb * block_k
            kblk = k_ref[0, pl.ds(k_start, block_k), :].astype(jnp.float32)
            vblk = v_ref[0, pl.ds(k_start, block_k), :].astype(jnp.float32)
            s = jax.lax.dot_general(
                q, kblk, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
            ) * scale
            needs_pad_mask = k_len_actual < k_len
            if masked and (causal or needs_pad_mask):
                k_ids = k_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
                valid = (k_ids < k_len_actual) if needs_pad_mask else True
                if causal:
                    q_ids = q_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
                    valid = valid & (q_ids >= k_ids)
                s = jnp.where(valid, s, DEFAULT_MASK_VALUE)
            p = jnp.exp(s - lse[:, None])  # masked entries underflow to 0
            dp = jax.lax.dot_general(
                do, vblk, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
            )
            ds = p * (dp - delta[:, None])
            return acc + jax.lax.dot_general(
                ds, kblk, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
            )

        return body

    init = jnp.zeros((block_q, head_dim), jnp.float32)
    if causal:
        # Same two-phase split + clamp as the forward kernel (see there).
        num_full = jnp.minimum(
            jax.lax.div(q_start, block_k), k_len_actual // block_k
        )
        acc = jax.lax.fori_loop(0, num_full, make_body(False), init)
        acc = jax.lax.fori_loop(num_full, num_k_blocks_needed, make_body(True), acc)
    else:
        acc = jax.lax.fori_loop(0, num_k_blocks_needed, make_body(True), init)
    dq_ref[0] = (acc * scale).astype(dq_ref.dtype)


def _flash_bwd_dkv_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref, dv_ref, *,
    block_q: int, causal: bool, scale: float, grouped: bool = False
):
    """One (batch·kv_head, k-block[, group]) program: dK/dV accumulated
    over q blocks.

    GQA (``grouped``): a third, innermost grid dim walks the kv head's
    group of q heads; each program sees ONE q head's (padded) rows — the
    same VMEM footprint as MHA — and accumulates into the shared
    (batch·kv_head, k-block) output block, which stays resident across
    the group steps (output index map constant along the group dim).

    Padded q rows (q/do/delta zero-padded, lse zero) contribute nothing:
    dO = 0 kills the dV term and dP − Δ = 0 kills the dK term.
    """
    k = k_ref[0].astype(jnp.float32)  # [block_k, d]
    v = v_ref[0].astype(jnp.float32)
    block_k, head_dim = k.shape
    q_len = q_ref.shape[1]  # one q head's rows, padded to block_q multiple
    k_start = pl.program_id(1) * block_k
    num_q_blocks = q_len // block_q
    # Causal: q blocks strictly before this k block see none of it.
    start_qb = jax.lax.div(k_start, block_q) if causal else 0

    def make_body(masked: bool):
        def body(qb, carry):
            dk, dv = carry
            q_start = qb * block_q
            qblk = q_ref[0, pl.ds(q_start, block_q), :].astype(jnp.float32)
            doblk = do_ref[0, pl.ds(q_start, block_q), :].astype(jnp.float32)
            lse = lse_ref[0, 0, pl.ds(q_start, block_q)].astype(jnp.float32)
            delta = delta_ref[0, 0, pl.ds(q_start, block_q)].astype(jnp.float32)
            s = jax.lax.dot_general(
                qblk, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
            ) * scale
            if masked and causal:
                q_ids = q_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
                k_ids = k_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
                s = jnp.where(q_ids >= k_ids, s, DEFAULT_MASK_VALUE)
            p = jnp.exp(s - lse[:, None])
            dv = dv + jax.lax.dot_general(
                p, doblk, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
            )
            dp = jax.lax.dot_general(
                doblk, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
            )
            ds = p * (dp - delta[:, None])
            dk = dk + jax.lax.dot_general(
                ds, qblk, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
            )
            return dk, dv

        return body

    init = (
        jnp.zeros((block_k, head_dim), jnp.float32),
        jnp.zeros((block_k, head_dim), jnp.float32),
    )
    if causal:
        # Masked head phase: q blocks overlapping this k block's diagonal
        # span; everything after q_start >= k_start + block_k is fully
        # above the diagonal and needs no mask.
        first_full = jnp.minimum(
            jax.lax.div(k_start + block_k + block_q - 1, block_q), num_q_blocks
        )
        carry = jax.lax.fori_loop(start_qb, first_full, make_body(True), init)
        dk, dv = jax.lax.fori_loop(first_full, num_q_blocks, make_body(False), carry)
    else:
        dk, dv = jax.lax.fori_loop(start_qb, num_q_blocks, make_body(True), init)
    dk = dk * scale
    if grouped:
        # fp32 outputs accumulate across the group grid dim
        @pl.when(pl.program_id(2) == 0)
        def _init():
            dk_ref[0] = dk.astype(dk_ref.dtype)
            dv_ref[0] = dv.astype(dv_ref.dtype)

        @pl.when(pl.program_id(2) != 0)
        def _acc():
            dk_ref[0] += dk.astype(dk_ref.dtype)
            dv_ref[0] += dv.astype(dv_ref.dtype)
    else:
        dk_ref[0] = dk.astype(dk_ref.dtype)
        dv_ref[0] = dv.astype(dv_ref.dtype)


def _flash_backward(q, k, v, o, lse, do, causal: bool, scale: float,
                    block_q: int, block_k: int, interpret: bool):
    batch, heads, q_len, head_dim = q.shape
    kv_heads = k.shape[1]
    group = heads // kv_heads
    k_len = k.shape[2]
    bq = min(block_q, q_len)
    bk = min(block_k, k_len)
    bh = batch * heads

    qr = q.reshape(bh, q_len, head_dim)
    kr = k.reshape(batch * kv_heads, k_len, head_dim)
    vr = v.reshape(batch * kv_heads, k_len, head_dim)
    dor = do.reshape(bh, q_len, head_dim)
    lser = lse.reshape(bh, 1, q_len)
    # Δ = rowsum(dO ∘ O): one fused elementwise+reduce, cheap in XLA.
    delta = jnp.sum(
        dor.astype(jnp.float32) * o.reshape(bh, q_len, head_dim).astype(jnp.float32),
        axis=-1,
    ).reshape(bh, 1, q_len)

    k_pad = (-k_len) % bk
    if k_pad:
        kr = jnp.pad(kr, ((0, 0), (0, k_pad), (0, 0)))
        vr = jnp.pad(vr, ((0, 0), (0, k_pad), (0, 0)))
    k_len_p = k_len + k_pad
    if not interpret:
        _check_resident_fits("K/V", k_len_p, head_dim, k.dtype)
    kv_map = _kv_index_map(heads, kv_heads)

    # dQ: grid over q blocks, K/V resident (GQA: shared kv head indexed).
    dq = pl.pallas_call(
        functools.partial(
            _flash_bwd_dq_kernel, block_k=bk, causal=causal, scale=scale,
            k_len_actual=k_len,
        ),
        grid=(bh, pl.cdiv(q_len, bq)),
        in_specs=[
            pl.BlockSpec((1, bq, head_dim), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, k_len_p, head_dim), kv_map),
            pl.BlockSpec((1, k_len_p, head_dim), kv_map),
            pl.BlockSpec((1, bq, head_dim), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, 1, bq), lambda b, i: (b, 0, i)),
            pl.BlockSpec((1, 1, bq), lambda b, i: (b, 0, i)),
        ],
        out_specs=pl.BlockSpec((1, bq, head_dim), lambda b, i: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, q_len, head_dim), q.dtype),
        interpret=interpret,
        name="flash_bwd_dq",
    )(qr, kr, vr, dor, lser, delta)

    # dK/dV: grid over (batch·kv_heads, k blocks[, q-head group]); each
    # program streams ONE q head's blocks (same VMEM footprint as MHA);
    # for GQA the group is the innermost grid dim and dK/dV accumulate in
    # the resident fp32 output block. Q-side arrays must be padded to a
    # block_q multiple for the dynamic slices (padded rows are harmless
    # per the kernel docstring).
    q_pad = (-q_len) % bq
    if q_pad:
        qr = jnp.pad(qr, ((0, 0), (0, q_pad), (0, 0)))
        dor = jnp.pad(dor, ((0, 0), (0, q_pad), (0, 0)))
        lser = jnp.pad(lser, ((0, 0), (0, 0), (0, q_pad)))
        delta = jnp.pad(delta, ((0, 0), (0, 0), (0, q_pad)))
    q_len_p = q_len + q_pad
    if not interpret:
        _check_resident_fits("Q/dO", q_len_p, head_dim, q.dtype)
        if bq % 128:
            # dK/dV slices lse/delta along the lane axis at a dynamic
            # q-block offset; Mosaic needs that offset provably 128-aligned
            # ("cannot statically prove that index in dimension 2 is a
            # multiple of 128" otherwise).
            raise ValueError(
                f"flash_attention backward: q block {bq} (min of block_q "
                f"{block_q} and q_len {q_len}) must be a multiple of 128 on "
                f"TPU; pad the sequence to a multiple of 128"
            )

    if group > 1:
        bkv = batch * kv_heads
        # [b·H, q_len_p, d] -> [b·KV, group·q_len_p, d]; block index g on
        # the row axis selects one q head's segment
        qr = qr.reshape(bkv, group * q_len_p, head_dim)
        dor = dor.reshape(bkv, group * q_len_p, head_dim)
        lser = lser.reshape(bkv, 1, group * q_len_p)
        delta = delta.reshape(bkv, 1, group * q_len_p)
        grid = (bkv, k_len_p // bk, group)
        q_spec = pl.BlockSpec((1, q_len_p, head_dim), lambda b, j, g: (b, g, 0))
        r_spec = pl.BlockSpec((1, 1, q_len_p), lambda b, j, g: (b, 0, g))
        kv_in = pl.BlockSpec((1, bk, head_dim), lambda b, j, g: (b, j, 0))
        kv_out = pl.BlockSpec((1, bk, head_dim), lambda b, j, g: (b, j, 0))
        out_dtype = jnp.float32  # group accumulation stays full precision
    else:
        bkv = bh
        grid = (bkv, k_len_p // bk)
        q_spec = pl.BlockSpec((1, q_len_p, head_dim), lambda b, j: (b, 0, 0))
        r_spec = pl.BlockSpec((1, 1, q_len_p), lambda b, j: (b, 0, 0))
        kv_in = pl.BlockSpec((1, bk, head_dim), lambda b, j: (b, j, 0))
        kv_out = pl.BlockSpec((1, bk, head_dim), lambda b, j: (b, j, 0))
        out_dtype = None

    dk, dv = pl.pallas_call(
        functools.partial(
            _flash_bwd_dkv_kernel, block_q=bq, causal=causal, scale=scale,
            grouped=group > 1,
        ),
        grid=grid,
        in_specs=[q_spec, kv_in, kv_in, q_spec, r_spec, r_spec],
        out_specs=[kv_out, kv_out],
        out_shape=[
            jax.ShapeDtypeStruct((bkv, k_len_p, head_dim), out_dtype or k.dtype),
            jax.ShapeDtypeStruct((bkv, k_len_p, head_dim), out_dtype or v.dtype),
        ],
        interpret=interpret,
        name="flash_bwd_dkv",
    )(qr, kr, vr, dor, lser, delta)
    if k_pad:
        dk = dk[:, :k_len]
        dv = dv[:, :k_len]
    if group > 1:
        dk = dk.astype(k.dtype)
        dv = dv.astype(v.dtype)
    return (
        dq.reshape(batch, heads, q_len, head_dim),
        dk.reshape(batch, kv_heads, k_len, head_dim),
        dv.reshape(batch, kv_heads, k_len, head_dim),
    )


def _env_blocks(var: str):
    import os

    raw = os.environ.get(var)
    if not raw:
        return None
    bq, bk = raw.split(",")
    return int(bq), int(bk)


def _default_blocks(q_len: int, k_len: int, head_dim: int, bwd: bool = False):
    """Shape-adaptive Pallas block sizes, measured on v5e (bf16):
    (1024, 512) beats (256, 256) by ~35-40%% at head_dim 64 across
    2k-8k sequence; at head_dim 128 (512, 512) beats (512, 256) by ~4
    points of end-to-end train MFU on the 750M flagship bench, and the
    round-3 sweep (benchmarks/tune_flash.py) confirmed it still wins
    against (1024,512)/(512,1024)/(256,512) variants there.
    Larger head dims multiply per-program VMEM (blocks plus the resident
    K/V), so they step down conservatively.

    Env overrides for tuning sweeps: RAY_TPU_FLASH_BLOCKS="bq,bk" and
    RAY_TPU_FLASH_BWD_BLOCKS="bq,bk" (backward kernels only)."""
    override = _env_blocks("RAY_TPU_FLASH_BWD_BLOCKS" if bwd else "RAY_TPU_FLASH_BLOCKS")
    if override is None and bwd:
        override = _env_blocks("RAY_TPU_FLASH_BLOCKS")
    if override is not None:
        return override
    if head_dim <= 64:
        return 1024, 512
    if head_dim <= 128:
        return 512, 512
    return 256, 256


def _use_pallas() -> bool:
    """Pallas kernels when the default backend is a TPU; the jnp
    reference on CPU (tier-1 tests run there). No other fallback: a TPU
    backend that fails to come up raises here."""
    import os

    # AOT compiles against a TPU *topology* run with a CPU default
    # backend — the env override lets them force the TPU lowering
    # (benchmarks/compile_7b.py --backend tpu, tests/test_chip_bringup.py).
    force = os.environ.get("RAY_TPU_FORCE_PALLAS")
    if force is not None:
        return force == "1"
    return jax.default_backend() == "tpu"


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def flash_attention(q, k, v, causal: bool = True, scale: Optional[float] = None):
    """Flash attention: Pallas kernels on TPU, jnp reference elsewhere."""
    return _fwd(q, k, v, causal, scale)[0]


# The names under which the forward kernel's two results are known to a
# ``jax.checkpoint`` policy (models/transformer.py::checkpoint_layer keeps
# them, so a rematerialised layer never runs the kernel a second time).
FLASH_RESIDUAL_NAMES = ("flash_out", "flash_lse")


def _fwd(q, k, v, causal, scale):
    s = scale if scale is not None else q.shape[-1] ** -0.5
    if _use_pallas():
        bq, bk = _default_blocks(q.shape[-2], k.shape[-2], q.shape[-1])
        out, lse = _flash_forward(q, k, v, causal, s, block_q=bq, block_k=bk, interpret=False)
        # Named HERE, before they become both the primal result and the
        # residuals: the variables the backward kernels read are then the
        # named ones, and a policy that keeps the names keeps the kernel
        # from running again. Outside a checkpoint a name is the identity.
        out = checkpoint_name(out, FLASH_RESIDUAL_NAMES[0])
        lse = checkpoint_name(lse, FLASH_RESIDUAL_NAMES[1])
        return out, (q, k, v, out, lse)
    return reference_attention(q, k, v, causal=causal, scale=s), (q, k, v, None, None)


def _bwd(causal, scale, res, g):
    q, k, v, o, lse = res
    s = scale if scale is not None else q.shape[-1] ** -0.5
    if o is not None:
        bq, bk = _default_blocks(q.shape[-2], k.shape[-2], q.shape[-1], bwd=True)
        return _flash_backward(
            q, k, v, o, lse, g, causal, s, block_q=bq, block_k=bk, interpret=False
        )

    # Non-TPU: recompute via the reference path; XLA fuses the softmax chain.
    def ref(q, k, v):
        return reference_attention(q, k, v, causal=causal, scale=s)

    _, vjp = jax.vjp(ref, q, k, v)
    return vjp(g)


flash_attention.defvjp(_fwd, _bwd)


def unmanual_axes(mesh):
    """(mesh to shard_map over, its axes not yet manual). Inside another
    shard_map (the pp pipeline body) the AMBIENT abstract mesh replaces
    the construction-time ``mesh``: it marks which axes are already
    manual. Mosaic's lowering requires the union of manual axes to cover
    EVERY mesh axis (tpu_custom_call.py), so a shard_map around a Pallas
    kernel manualizes all of the returned axes; size-1 axes cost
    nothing."""
    from jax.sharding import AxisType

    cur = jax.sharding.get_abstract_mesh()
    use = cur if cur.shape else mesh
    return use, {
        n for n, t in zip(use.axis_names, use.axis_types) if t != AxisType.Manual
    }


def make_flash_attn_fn(mesh, causal: bool = True, scale: Optional[float] = None):
    """Flash attention for MULTI-DEVICE meshes: Mosaic (Pallas) kernels
    cannot be auto-partitioned by GSPMD, so the kernel must run inside a
    shard_map that makes the batch/head axes manual — each device runs
    the kernel on its local [b/(dp·fsdp), h/tp, s, d] shard (sequence
    stays whole; sp>1 uses ring/Ulysses instead). Falls back to a direct
    call on single-device meshes and when no known axes are present.

    Same construction-time-mesh/ambient-mesh convention as
    ring.make_ring_attn_fn so it nests under the pp pipeline shard_map;
    ``mesh`` may be None for a caller that is always inside a shard_map
    (the Ulysses per-shard body).
    """

    def attn(q, k, v):
        use, manual = unmanual_axes(mesh)
        if use.size <= 1 or not manual:
            # one device, or a fully-manual context: data is already local
            return flash_attention(q, k, v, causal, scale)
        batch_axes = tuple(a for a in ("dp", "fsdp") if a in manual)
        head_axis = None
        if "tp" in manual:
            tp_size = dict(use.shape)["tp"]
            if q.shape[1] % tp_size == 0:
                head_axis = "tp"
                if k.shape[1] != q.shape[1] and k.shape[1] % tp_size:
                    # kv heads don't shard over tp: expand to MHA so each
                    # tp shard's local q↔kv mapping stays contiguous
                    # (native GQA under tp requires tp | kv_heads)
                    rep = q.shape[1] // k.shape[1]
                    k = jnp.repeat(k, rep, axis=1)
                    v = jnp.repeat(v, rep, axis=1)
            # else: heads don't divide tp — leave them unsharded; each tp
            # shard computes all heads (redundant but correct, like the
            # GSPMD partial-replication this replaces)
        from jax.sharding import PartitionSpec as P

        qspec = P(batch_axes or None, head_axis, None, None)
        fn = jax.shard_map(
            lambda q, k, v: flash_attention(q, k, v, causal, scale),
            mesh=use,
            in_specs=(qspec, qspec, qspec),
            out_specs=qspec,
            axis_names=manual,
            check_vma=False,
        )
        return fn(q, k, v)

    attn.supports_gqa = True  # kernel handles kv_heads != q_heads natively
    return attn
