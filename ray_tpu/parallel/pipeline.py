"""Pipeline parallelism: microbatched GPipe over the ``pp`` mesh axis.

The reference provides pipeline *transport/scheduling* only (SURVEY.md §2.9
— compiled-DAG NCCL channels + op-graph overlap, dag/dag_node_operation.py);
the TPU-native version is in-graph: the layer stack is reshaped to
[n_stages, layers_per_stage, ...] with the stage axis sharded over ``pp``,
and a shard_map (manual only over ``pp``; dp/fsdp/tp/sp stay automatic so
GSPMD keeps inserting their collectives inside the stage body) runs the
classic GPipe schedule — microbatches march through stages via
``lax.ppermute`` activation hand-offs over ICI neighbor links (cf. the MPMD
pipeline paper in PAPERS.md; this is its SPMD collective-permute variant).

Cost model: bubble fraction = (S-1)/(M+S-1); every stage computes every
step (idle steps compute on zeros) which XLA overlaps with the permute.
"""
from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def _pipeline_body(stage_params, stage_ids, h_mb, positions, *, stage_fn, num_stages, num_microbatches, axis_name):
    """shard_map body. stage_params: [1, L/S, ...] (local stage shard);
    stage_ids: [1] this stage's index (an arange sharded over pp —
    lax.axis_index lowers to a PartitionId op that the SPMD partitioner
    rejects inside a partially-manual shard_map on older jax);
    h_mb: [M, mb, s, d] microbatched activations (auto-sharded on batch)."""
    p = stage_ids[0]
    M, S = num_microbatches, num_stages
    params_local = jax.tree.map(lambda x: x[0], stage_params)
    is_first = p == 0
    is_last = p == S - 1
    zero = jnp.zeros_like(h_mb[0])

    def step(carry, t):
        pipe_reg, outputs = carry
        # Stage 0 feeds microbatch t (clamped); other stages use the register.
        mb_idx = jnp.clip(t, 0, M - 1)
        first_in = jax.lax.dynamic_index_in_dim(h_mb, mb_idx, axis=0, keepdims=False)
        x_in = jnp.where(is_first, first_in, pipe_reg)
        active = jnp.logical_and(t >= p, t - p < M)
        out = stage_fn(params_local, x_in, positions)
        out = jnp.where(active, out, zero)
        # Forward hand-off: stage i → i+1 (no wraparound; stage 0 receives 0s).
        perm = [(i, i + 1) for i in range(S - 1)]
        nxt = jax.lax.ppermute(out, axis_name, perm)
        # Last stage banks its finished microbatch at slot t-(S-1).
        out_idx = jnp.clip(t - (S - 1), 0, M - 1)
        bank = jnp.logical_and(is_last, active)
        onehot = (jnp.arange(M) == out_idx).astype(out.dtype) * jnp.where(bank, 1.0, 0.0).astype(out.dtype)
        outputs = outputs + onehot[:, None, None, None] * out[None]
        return (nxt, outputs), None

    init = (zero, jnp.zeros_like(h_mb))
    (_, outputs), _ = jax.lax.scan(step, init, jnp.arange(M + S - 1))
    # Everyone needs the result (loss/unembed run data-parallel afterwards):
    # only the last stage holds non-zeros, so a psum over pp broadcasts it.
    return jax.lax.psum(outputs, axis_name)


def pipeline_apply(
    stage_fn: Callable,
    stacked_stage_params,
    h,
    positions,
    mesh: Mesh,
    num_stages: int,
    num_microbatches: int,
    axis_name: str = "pp",
    seq_axis: str = None,
):
    """Run h [b, s, d] through the pipelined decoder stack.

    stage_fn(params_one_stage, x, positions) -> x, where params_one_stage
    has leading dim layers_per_stage. ``stacked_stage_params`` has leading
    dims [num_stages, layers_per_stage] with the stage axis sharded over pp.

    ``seq_axis``: when sequence parallelism composes with pp, the pipeline
    shard_map goes manual over BOTH axes (nested partial-manual shard_maps
    don't lower) — the sequence dim arrives pre-sharded and the stage_fn's
    attention must be the RAW per-shard collective body
    (ring_attention_local / ulysses_attention_local), whose ppermute/
    all_to_all run directly in this manual context.
    """
    b = h.shape[0]
    assert b % num_microbatches == 0, (b, num_microbatches)
    mb = b // num_microbatches
    h_mb = h.reshape(num_microbatches, mb, *h.shape[1:])
    pos_mb = positions[:mb]

    # Manual only over pp (+ seq_axis when composing with sp); remaining
    # axes stay automatic so GSPMD keeps inserting fsdp/tp collectives
    # inside the stage body.
    h_spec = P(None, None, seq_axis, None) if seq_axis else P()
    pos_spec = P(None, seq_axis) if seq_axis else P()
    manual = {axis_name} | ({seq_axis} if seq_axis else set())
    body = jax.shard_map(
        functools.partial(
            _pipeline_body,
            stage_fn=stage_fn,
            num_stages=num_stages,
            num_microbatches=num_microbatches,
            axis_name=axis_name,
        ),
        mesh=mesh,
        in_specs=(
            jax.tree.map(lambda _: P(axis_name), stacked_stage_params),
            P(axis_name),
            h_spec,
            pos_spec,
        ),
        out_specs=h_spec,
        axis_names=manual,
        check_vma=False,
    )
    stage_ids = jnp.arange(num_stages, dtype=jnp.int32)
    out = body(stacked_stage_params, stage_ids, h_mb, pos_mb)
    return out.reshape(b, *h.shape[1:])


def split_stages(layer_params, num_stages: int):
    """[L, ...] stacked layer params → [S, L/S, ...]."""

    def rs(x):
        L = x.shape[0]
        assert L % num_stages == 0, (L, num_stages)
        return x.reshape(num_stages, L // num_stages, *x.shape[1:])

    return jax.tree.map(rs, layer_params)
