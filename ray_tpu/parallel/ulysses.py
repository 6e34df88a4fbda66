"""Ulysses sequence parallelism: all-to-all head/sequence swaps.

The reference has NO sequence/context parallelism (SURVEY.md §5.7 —
repo-wide grep finds none; its closest primitives are NCCL p2p channels,
python/ray/util/collective/collective.py:531). Here it is native, as the
second SP strategy next to ring attention (ray_tpu/parallel/ring.py):

Each device holds a ``[b, h, s/sp, d]`` shard. One ``lax.all_to_all``
over the ``sp`` axis re-shards from sequence-split to head-split
(``[b, h/sp, s, d]``), every device then runs *full-sequence* attention
over its head subset — so the single-chip flash-attention pallas kernel
(ray_tpu/ops/attention.py) applies unchanged — and a second all-to-all
swaps back. Two all-to-alls per attention call vs ring's sp-1 ppermute
rounds: Ulysses wins when sp divides the local head count and the
per-hop latency dominates (short sequences, large sp); ring wins at very
long sequence where overlap of compute with neighbor-hop transfers
matters.

Both ride ICI when ``sp`` maps to a physical torus axis.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from ray_tpu.ops.attention import make_flash_attn_fn, unmanual_axes


def ulysses_attention_local(q, k, v, axis_name: str, scale: Optional[float] = None,
                            causal: bool = True):
    """Per-shard body — call inside shard_map with q,k,v local shards
    ``[b, h, s_local, d]``. Requires ``h % sp == 0`` (heads per device
    after any tp split must still divide sp)."""
    sp = jax.lax.axis_size(axis_name)
    h = q.shape[1]
    if h % sp != 0:
        raise ValueError(
            f"Ulysses SP needs local heads ({h}) divisible by sp ({sp}); "
            "use ring attention for head counts that don't split"
        )

    # One collective for all three tensors: stack on a leading axis so the
    # latency-dominated regime this mode targets pays a single all-to-all
    # launch instead of three.
    qkv = jnp.stack([q, k, v])  # [3, b, h, s/sp, d]
    qkv = jax.lax.all_to_all(qkv, axis_name, split_axis=2, concat_axis=3, tiled=True)
    qh, kh, vh = qkv  # each [b, h/sp, s, d]
    # Under the pp pipeline only pp and sp are manual here; the Pallas
    # kernel needs the rest manual too (a direct call when they all are).
    out = make_flash_attn_fn(None, causal, scale)(qh, kh, vh)
    # [b, h/sp, s, d] -> [b, h, s/sp, d]
    return jax.lax.all_to_all(out, axis_name, split_axis=2, concat_axis=1, tiled=True)


def make_ulysses_attn_fn(mesh: Mesh, axis_name: str = "sp"):
    """An attn_fn for models.transformer: [b,h,s,d] global → Ulysses
    attention over the ``axis_name`` shards. Must run inside a jit whose
    inputs are sharded over this mesh. Same signature/specs as
    ring.make_ring_attn_fn so the two are drop-in alternatives."""
    spec = P(("dp", "fsdp"), "tp", axis_name, None)
    body = functools.partial(ulysses_attention_local, axis_name=axis_name)

    def attn(q, k, v):
        # Nestable under a pp shard_map like ring.make_ring_attn_fn, but
        # the body runs the Pallas flash kernel, so EVERY axis not yet
        # manual is manualized (ep/pp too), not only the four in ``spec``.
        use, manual = unmanual_axes(mesh)
        fn = jax.shard_map(
            body,
            mesh=use,
            in_specs=(spec, spec, spec),
            out_specs=spec,
            axis_names=manual,
            check_vma=False,
        )
        return fn(q, k, v)

    return attn
