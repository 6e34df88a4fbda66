"""In-graph XLA collectives: the TPU fast path.

The reference's NCCL group (python/ray/util/collective/collective_group/
nccl_collective_group.py) launches per-call CUDA kernels; on TPU there is
no eager collective — collectives are *compiled into* the program and ride
ICI. So the XLA "group" hands out the two things a compiled program needs:
a ``jax.sharding.Mesh`` and an axis name. User code then writes

    mesh, axis = xla_group.mesh_for_group("g")
    @functools.partial(shard_map, mesh=mesh, in_specs=P(axis), out_specs=P())
    def step(x):
        return lax.psum(x, axis)

and XLA lowers psum onto the ICI ring. ``in_graph_allreduce`` below is the
ready-made wrapper for the common case.
"""
from __future__ import annotations

import functools
from typing import Optional, Sequence, Tuple


def mesh_for_group(
    group_name: str = "default",
    axis_name: str = "ranks",
    devices: Optional[Sequence] = None,
):
    """Build a 1-axis Mesh over this process's devices for in-graph
    collectives. For multi-host meshes use ray_tpu.parallel.MeshPlan with a
    gang-scheduled worker group (SURVEY.md §7 hard parts)."""
    import jax
    from jax.sharding import Mesh
    import numpy as np

    devs = list(devices) if devices is not None else jax.devices()
    return Mesh(np.array(devs), (axis_name,)), axis_name


def in_graph_allreduce(x, mesh=None, axis_name: str = "ranks"):
    """Jitted psum over a device mesh: ``x``'s leading axis is sharded
    across devices and fully reduced (local sum + psum); result replicated."""
    import jax
    from jax import lax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ray_tpu.collective import diagnostics
    if mesh is None:
        mesh, axis_name = mesh_for_group(axis_name=axis_name)

    @functools.partial(
        jax.shard_map, mesh=mesh, in_specs=P(axis_name), out_specs=P()
    )
    def _psum(shard):
        return lax.psum(shard.sum(axis=0), axis_name)

    # Times the DISPATCH only (compile included on first call — the
    # compile tracker attributes that part): blocking on the result here
    # would force a host sync on a hot path purely for a gauge. Rank 0 =
    # this process; in-graph collectives are SPMD within it.
    with diagnostics.timed_op(
        f"xla:{axis_name}", "in_graph_allreduce", 0, getattr(x, "nbytes", None)
    ):
        x = jax.device_put(x, NamedSharding(mesh, P(axis_name)))
        return jax.jit(_psum)(x)
