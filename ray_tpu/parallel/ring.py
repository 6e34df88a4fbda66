"""Ring attention: exact causal attention over a sequence-sharded mesh axis.

The reference has NO sequence/context parallelism (SURVEY.md §5.7 — absent;
its closest primitives are NCCL p2p channels). Here it is native: each
device holds a [b, h, s/sp, d] shard of Q, K, V; K/V shards rotate around
the ``sp`` ring via ``lax.ppermute`` while every device accumulates its
queries' attention with a running (max, sum) online-softmax merge — the
blockwise/ring attention construction (cf. PAPERS.md ring-topology entries),
riding ICI neighbor links on a real pod.

Causality across shards is handled at shard granularity: with q-shard index
i attending k-shard index j, j>i contributes nothing, j==i is causally
masked, j<i is full attention.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from ray_tpu.ops.attention import DEFAULT_MASK_VALUE


def _block_attend(q, k, v, scale, mode):
    """Partial attention of one (q-shard, k-shard) pair.

    Returns (numerator [b,h,sq,d], row_max [b,h,sq], row_sum [b,h,sq]).
    mode: 0 = masked-out entirely, 1 = causal within block, 2 = full.
    """
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k, preferred_element_type=jnp.float32) * scale
    sq, sk = q.shape[-2], k.shape[-2]

    def causal(s):
        ids_q = jax.lax.broadcasted_iota(jnp.int32, (sq, sk), 0)
        ids_k = jax.lax.broadcasted_iota(jnp.int32, (sq, sk), 1)
        return jnp.where(ids_q >= ids_k, s, DEFAULT_MASK_VALUE)

    s = jax.lax.switch(
        mode,
        [lambda s: jnp.full_like(s, DEFAULT_MASK_VALUE), causal, lambda s: s],
        s,
    )
    m = jnp.max(s, axis=-1)
    p = jnp.exp(s - m[..., None])
    l = jnp.sum(p, axis=-1)
    num = jnp.einsum("bhqk,bhkd->bhqd", p.astype(v.dtype), v).astype(jnp.float32)
    return num, m, l


def ring_attention_local(q, k, v, axis_name: str, scale: Optional[float] = None):
    """Per-shard body — call inside shard_map with q,k,v local shards
    [b, h, s_local, d]."""
    scale = scale if scale is not None else q.shape[-1] ** -0.5
    sp = jax.lax.axis_size(axis_name)
    my_idx = jax.lax.axis_index(axis_name)
    b, h, sq, d = q.shape

    def merge(carry, k_cur, v_cur, r):
        acc, m_run, l_run = carry
        src_idx = (my_idx - r) % sp  # whose K/V shard we currently hold
        mode = jnp.where(src_idx == my_idx, 1, jnp.where(src_idx < my_idx, 2, 0))
        num, m_blk, l_blk = _block_attend(q, k_cur, v_cur, scale, mode)
        m_new = jnp.maximum(m_run, m_blk)
        c_run = jnp.exp(m_run - m_new)
        c_blk = jnp.exp(m_blk - m_new)
        acc = acc * c_run[..., None] + num * c_blk[..., None]
        l_run = l_run * c_run + l_blk * c_blk
        return acc, m_new, l_run

    perm = [(i, (i + 1) % sp) for i in range(sp)]

    def step(carry, r):
        k_cur, v_cur, inner = carry
        inner = merge(inner, k_cur, v_cur, r)
        k_nxt = jax.lax.ppermute(k_cur, axis_name, perm)
        v_nxt = jax.lax.ppermute(v_cur, axis_name, perm)
        return (k_nxt, v_nxt, inner), None

    inner0 = (
        jnp.zeros((b, h, sq, d), jnp.float32),
        jnp.full((b, h, sq), -jnp.inf, jnp.float32),
        jnp.zeros((b, h, sq), jnp.float32),
    )
    # sp-1 attend+rotate steps, then a final attend with no rotation —
    # exactly sp-1 ppermute pairs instead of sp.
    (k, v, inner), _ = jax.lax.scan(step, (k, v, inner0), jnp.arange(sp - 1))
    acc, m_run, l_run = merge(inner, k, v, sp - 1)
    out = acc / jnp.maximum(l_run, 1e-30)[..., None]
    return out.astype(q.dtype)


def make_ring_attn_fn(mesh: Mesh, axis_name: str = "sp"):
    """An attn_fn for models.transformer: [b,h,s,d] global → ring attention
    over the ``axis_name`` shards. Must run inside a jit whose inputs are
    sharded over this mesh.

    Nestable under another shard_map (the pp pipeline body): at trace
    time the AMBIENT abstract mesh — whose already-manual axes (pp) are
    marked as such — is used instead of the concrete construction-time
    mesh, and only the axes this collective touches are manualized."""
    spec = P(("dp", "fsdp"), "tp", axis_name, None)
    body = functools.partial(ring_attention_local, axis_name=axis_name)

    def attn(q, k, v):
        cur = jax.sharding.get_abstract_mesh()
        use = cur if cur.shape else mesh
        fn = jax.shard_map(
            body,
            mesh=use,
            in_specs=(spec, spec, spec),
            out_specs=spec,
            axis_names={"dp", "fsdp", "tp", axis_name},
            check_vma=False,
        )
        return fn(q, k, v)

    return attn
