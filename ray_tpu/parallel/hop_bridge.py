"""Cross-process device-to-device activation transfer (the DCN leg).

Reference: python/ray/experimental/channel/torch_tensor_nccl_channel.py
:190 and nccl_group.py:23 — the reference moves device tensors between
nodes with NCCL p2p send/recv. The TPU-native equivalent is NOT a
point-to-point kernel API (XLA owns the fabric): it is a tiny SPMD
program over the union of the two device groups that both sides dispatch
jointly, letting XLA route the bytes over ICI/DCN (gloo on the CPU
simulation). This is the "collective-bridge program per hop" design.

Mechanics: a 2-row mesh ``[[src...], [dst...]]`` with axes
("hop", "within"); the payload is a global array of shape
``(2, *shape)`` sharded ``P("hop")`` — row 0 holds the sender's value
(resident on src devices), row 1 a dummy. One ``ppermute`` along "hop"
moves row 0 onto the dst row; the receiver reads its addressable shard.
Every process owning src or dst devices MUST call :meth:`transfer` at
the same point in its schedule (it is a collective). A single process
owning both rows degenerates to a local copy — the same code path runs
single- and multi-process.
"""
from __future__ import annotations

import functools
from typing import Any, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def commit_replicated(arr, devices, sharding: Optional[Any] = None):
    """Place host data replicated onto a device row that may span
    processes: a sole-owner row takes the direct ``device_put``; a
    multi-process row assembles the global array from each process's
    identical local copy."""
    arr = np.asarray(arr)
    devices = list(devices)
    if sharding is None:
        sharding = NamedSharding(Mesh(np.array(devices), ("r",)), P())
    pid = jax.process_index()
    if all(d.process_index == pid for d in devices):
        return jax.device_put(arr, sharding)
    return jax.make_array_from_process_local_data(sharding, arr)


class HopBridge:
    """Device-group → device-group transfer inside one jax runtime
    (single- or multi-process via ``jax.distributed``).

    ``src_devices`` / ``dst_devices``: equal-length device lists. Values
    transferred must be replicated across their group (the MPMD stage
    contract: stage-internal sharding is handled by the stage program,
    the handoff carries the stage's replicated activations; a
    within-sharded variant threads the "within" mesh axis through
    ``within_spec``).
    """

    def __init__(self, src_devices: Sequence[Any], dst_devices: Sequence[Any],
                 within_spec: Optional[P] = None):
        assert len(src_devices) == len(dst_devices), (
            "hop bridge rows must be equal-length; pad the narrower stage "
            f"(got {len(src_devices)} src vs {len(dst_devices)} dst)"
        )
        self.src_devices = list(src_devices)
        self.dst_devices = list(dst_devices)
        self.mesh = Mesh(
            np.array([self.src_devices, self.dst_devices]), ("hop", "within")
        )
        # P("hop") on the leading payload axis; remaining dims replicated
        # (or within-sharded when within_spec names the "within" axis).
        if within_spec is None:
            spec = P("hop")
        else:
            spec = P("hop", *within_spec)
        self._spec = spec
        self.sharding = NamedSharding(self.mesh, spec)

        @functools.partial(
            jax.shard_map, mesh=self.mesh, in_specs=spec, out_specs=spec
        )
        def _fwd(x):
            return jax.lax.ppermute(x, "hop", [(0, 1)])

        @functools.partial(
            jax.shard_map, mesh=self.mesh, in_specs=spec, out_specs=spec
        )
        def _rev(x):
            return jax.lax.ppermute(x, "hop", [(1, 0)])

        self._bridge = {False: jax.jit(_fwd), True: jax.jit(_rev)}
        my_pid = jax.process_index()
        self._my_src = [d for d in self.src_devices if d.process_index == my_pid]
        self._my_dst = [d for d in self.dst_devices if d.process_index == my_pid]
        self._zeros_cache = {}

    # ------------------------------------------------------------------
    def _blocks_for(self, devices, value, shape, dtype):
        """Per-device [1, *shape] blocks. ``value`` replicated over its
        group → every local device holds a full copy we can reshape in
        place; dummy rows come from a cached zeros block."""
        blocks = []
        if value is None:
            for d in devices:
                key = (d.id, shape, dtype)
                z = self._zeros_cache.get(key)
                if z is None:
                    z = jax.device_put(
                        jnp.zeros((1,) + tuple(shape), dtype=dtype), d
                    )
                    self._zeros_cache[key] = z
                blocks.append(z)
            return blocks
        per_dev = {s.device.id: s.data for s in value.addressable_shards}
        for d in devices:
            blk = per_dev.get(d.id)
            if blk is None:
                raise ValueError(
                    f"value for hop transfer has no shard on device {d}: "
                    "stage activations must be replicated over the stage "
                    "mesh before the handoff"
                )
            blocks.append(blk.reshape((1,) + tuple(shape)))
        return blocks

    def transfer(self, value: Optional[Any], shape, dtype, *,
                 reverse: bool = False):
        """One hop. Collective: every process owning bridge devices calls
        this at the same schedule point. ``value``: the group-replicated
        array on the SENDING side's processes (None elsewhere). Returns
        the received value (replicated over this process's receiving
        devices) on receiver-side processes, else None.
        ``reverse=True`` sends dst→src (the backward-grad direction)."""
        shape = tuple(shape)
        send_local = self._my_dst if reverse else self._my_src
        recv_local = self._my_src if reverse else self._my_dst
        if not send_local and not recv_local:
            return None  # not a participant in this hop
        blocks = []
        src_row = self._my_src
        dst_row = self._my_dst
        # row order must follow the mesh: row 0 = src devices, row 1 = dst
        blocks += self._blocks_for(
            src_row, value if (src_row and not reverse) else None, shape, dtype
        )
        blocks += self._blocks_for(
            dst_row, value if (dst_row and reverse) else None, shape, dtype
        )
        g = jax.make_array_from_single_device_arrays(
            (2,) + shape, self.sharding, blocks
        )
        out = self._bridge[reverse](g)
        if not recv_local:
            return None
        recv_set = set(recv_local)
        out_blocks = []
        for s in out.addressable_shards:
            if s.device in recv_set:
                out_blocks.append(s.data.reshape(shape))
        # reassemble as a replicated GLOBAL array over the receiving
        # group (each process contributes its addressable blocks) so a
        # multi-process stage sees its usual replicated placement
        recv_group = self.src_devices if reverse else self.dst_devices
        recv_mesh = Mesh(np.array(recv_group), ("r",))
        return jax.make_array_from_single_device_arrays(
            shape, NamedSharding(recv_mesh, P()), out_blocks
        )
