"""Multi-host JAX runtime rendezvous through the controller KV.

Reference precedent: python/ray/train/torch/xla/config.py:67-75,120 —
the XLA backend picks rank 0's address via env-var rendezvous and every
worker calls ``init_process_group("xla")``. Same shape here: rank 0
claims a coordinator port and publishes it under the gang's KV key;
every rank (including 0) then calls ``jax.distributed.initialize`` so
``jax.devices()`` spans all host processes and ``pjit`` programs run
SPMD across them (ICI/DCN collectives on real pods; gloo on the CPU
simulation used in tests).
"""
from __future__ import annotations

import logging
import socket
import time
from typing import Optional

logger = logging.getLogger("ray_tpu.train")

_KV_NS = "jax_rendezvous"


def _host_ip() -> str:
    from ray_tpu.utils.net import host_ip

    return host_ip()


def _free_port() -> int:
    s = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
    s.bind(("", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def setup_jax_distributed(
    world_rank: int,
    world_size: int,
    group_name: str,
    timeout_s: float = 60.0,
) -> str:
    """Initialize the cross-host JAX runtime for this gang. Returns the
    coordinator address. Call before any other jax use in the process."""
    from ray_tpu.experimental import internal_kv

    key = f"coordinator:{group_name}".encode()
    if world_rank == 0:
        addr = f"{_host_ip()}:{_free_port()}"
        internal_kv._internal_kv_put(key, addr.encode(), namespace=_KV_NS)
    else:
        deadline = time.monotonic() + timeout_s
        while True:
            raw = internal_kv._internal_kv_get(key, namespace=_KV_NS)
            if raw:
                addr = raw.decode()
                break
            if time.monotonic() > deadline:
                raise TimeoutError(
                    f"rank {world_rank}: no coordinator published for "
                    f"{group_name} within {timeout_s}s"
                )
            time.sleep(0.05)
    import jax

    jax.distributed.initialize(
        coordinator_address=addr,
        num_processes=world_size,
        process_id=world_rank,
    )
    # The coordinator handshake succeeding does not mean the accelerator
    # runtime spans the gang. libtpu builds its slice from TPU_* topology
    # variables, not from jax.distributed: four one-chip processes of one
    # v5e host each come up as a complete 1-chip slice (measured, libtpu
    # 0.0.34) and would train four unrelated models.
    if jax.device_count() == jax.local_device_count() and world_size > 1:
        raise RuntimeError(
            f"use_jax_distributed: rank {world_rank}/{world_size} joined the "
            f"coordinator at {addr} but its jax runtime holds only its own "
            f"{jax.local_device_count()} device(s). Processes that each own "
            "part of one TPU host's chips do not form one runtime here; give "
            "one worker all chips of a host (resources_per_worker={'TPU': n}) "
            "and use one worker per host."
        )
    logger.info(
        "jax.distributed up: rank %d/%d via %s (%d global devices)",
        world_rank, world_size, addr, len(jax.devices()),
    )
    return addr


def shutdown_jax_distributed() -> None:
    try:
        import jax

        jax.distributed.shutdown()
    except Exception:  # noqa: BLE001 — never initialized / already down
        pass
