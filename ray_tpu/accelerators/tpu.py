"""TPUAcceleratorManager.

Reference: python/ray/_private/accelerators/tpu.py:71 —
- chip detection via /dev/accel* and vfio (:98-117); the ONE detector,
  ``init()`` calls it
- ``TPU_VISIBLE_CHIPS`` isolation (:155-195) with valid per-host chip
  counts {1, 2, 4, 8} (:14 TPU_VALID_CHIP_OPTIONS); ``visible_chips_env``
  is the ONE place that turns chip indices into libtpu's variables
- GCE/GKE metadata pod-type lookup (:198-228)
- pod-slice resources: ``TPU-<pod_type>-head`` on worker 0 and a
  ``TPU-<pod_type>`` name resource on every pod host (:334-397) so
  STRICT_PACK placement groups gang-schedule whole slices.
"""
from __future__ import annotations

import errno
import glob
import logging
import os
import sys
import time
from typing import Dict, List, Optional, Sequence

logger = logging.getLogger("ray_tpu.tpu")

TPU_VALID_CHIP_OPTIONS = (1, 2, 4, 8)
TPU_VISIBLE_CHIPS_ENV = "TPU_VISIBLE_CHIPS"
TPU_ACCELERATOR_TYPE_ENV = "TPU_ACCELERATOR_TYPE"  # e.g. "v5p-64"
TPU_WORKER_ID_ENV = "TPU_WORKER_ID"
GCE_METADATA_URL = "http://metadata.google.internal/computeMetadata/v1/instance/attributes/"
# wait_for_chips: how often a held chip is tried again, and for how long. The
# longest a dead holder was seen to keep a chip is 16 s (a four-chip trainer
# giving back 32 GB of pinned mappings, PRs 36 and 37).
CHIP_POLL_S = 0.05
CHIP_WAIT_BOUND_S = 120.0


def jax_backend_initialized() -> bool:
    """True once THIS process has opened a jax backend — on a TPU host,
    once it owns its chips. Never imports jax and never opens the
    backend itself (asking jax for its devices would)."""
    if "jax" not in sys.modules:
        return False
    from jax._src import xla_bridge

    return xla_bridge.backends_are_initialized()


def _open_and_close(path: str) -> None:
    os.close(os.open(path, os.O_RDWR))


def _holder_of(path: str) -> str:
    """Who keeps ``path`` from being opened: the pid that has it among its
    open files; else a zombie that still has threads, which is what a dead
    TPU worker looks like while the kernel takes its mappings down (its table
    of open files is already empty by then: PR 37, on the chip)."""
    from ray_tpu.core.cluster_utils import proc_stat

    dying = []
    for pid in filter(str.isdigit, os.listdir("/proc")):
        try:
            for fd in os.listdir(f"/proc/{pid}/fd"):
                if os.readlink(f"/proc/{pid}/fd/{fd}") == path:
                    return f"pid {pid}"
        except OSError:
            pass  # gone while we looked, or not ours to read
        st = proc_stat(int(pid))
        if st and st.dying:
            dying.append(pid)
    if dying:
        return f"pid {', '.join(dying)} (exited, its threads still in the kernel)"
    return "no process this one can see"


class TPUAcceleratorManager:
    resource_name = "TPU"

    # -- detection ----------------------------------------------------------
    @staticmethod
    def detect_chips() -> tuple[int, str]:
        """(chips this host can open, where they were seen): one
        ``/dev/accel<N>`` per chip under the accel driver, else one
        ``/dev/vfio/<group>`` per chip passed through VFIO (the v5e hosts
        this repo runs on: ``/dev/vfio/2`` next to the ``/dev/vfio/vfio``
        control node). PCI sysfs is not used: it lists every chip of the
        physical host, including those this machine was not given."""
        n = len(glob.glob("/dev/accel[0-9]*"))
        if n:
            return n, "/dev/accel*"
        n = len(glob.glob("/dev/vfio/[0-9]*"))
        return n, "/dev/vfio/*" if n else "no /dev/accel* or /dev/vfio/<group>"

    @staticmethod
    def get_current_node_accelerator_type() -> Optional[str]:
        """Pod type, e.g. "v5p-64": env override first, then GCE metadata
        (reference: tpu.py:198-228 — metadata lookup with env fallbacks)."""
        env = os.environ.get(TPU_ACCELERATOR_TYPE_ENV)
        if env:
            return env
        try:
            import urllib.request

            req = urllib.request.Request(
                GCE_METADATA_URL + "accelerator-type",
                headers={"Metadata-Flavor": "Google"},
            )
            with urllib.request.urlopen(req, timeout=1) as r:
                return r.read().decode().strip()
        except Exception:  # noqa: BLE001 — not on GCE
            return None

    @staticmethod
    def get_current_node_tpu_worker_id() -> int:
        return int(os.environ.get(TPU_WORKER_ID_ENV, "0"))

    # -- isolation ----------------------------------------------------------
    @staticmethod
    def validate_resource_request_quantity(quantity: float) -> tuple[bool, str]:
        """Per-host chip requests must be 1/2/4/8 (reference: tpu.py:140)."""
        if quantity in TPU_VALID_CHIP_OPTIONS or quantity % 8 == 0:
            return True, ""
        return False, (
            f"num_tpus must be one of {TPU_VALID_CHIP_OPTIONS} per host "
            f"(or a multiple of 8 for multi-host slices); got {quantity}"
        )

    @staticmethod
    def visible_chips_env(chip_ids: List[int], chips_on_host: int) -> Dict[str, str]:
        """The variables that make exactly ``chip_ids`` this process's
        TPU. libtpu reads them once, when the process first opens the
        chip, so they must be in its environment before that (the
        controller puts them in a TPU actor's runtime env, applied before
        the actor's code is even unpickled).

        Established on a four-chip v5e host, libtpu 0.0.34 (PR 21):
        all chips — nothing, libtpu's default (and the host image's own
        TPU_* variables) describe that; ONE chip — which one, and the
        bounds of a one-chip, one-process slice (four such processes run
        side by side). Other subsets carry the reference's bounds
        unverified: two of four chips were refused with every pairing
        and bounds tried, so a 2-chip actor on a 4-chip host is broken."""
        n = len(chip_ids)
        if n == chips_on_host:
            return {}
        return {
            TPU_VISIBLE_CHIPS_ENV: ",".join(str(i) for i in chip_ids),
            # four v5e chips sit as a 2x2, not the reference's 1x4 row
            "TPU_CHIPS_PER_PROCESS_BOUNDS": "2,2,1" if n == 4 else f"1,{n},1",
            "TPU_PROCESS_BOUNDS": "1,1,1",
        }

    @staticmethod
    def chip_devices(chip_ids: Optional[List[int]]) -> List[str]:
        """The device nodes that opening ``chip_ids`` will open (``None``: every
        chip of the host, as ``visible_chips_env`` spells all of them): the
        host's VFIO groups, else nothing (``/dev/accel*``, CPU).

        Established on a four-chip v5e host, libtpu 0.0.34 (PR 37), one process
        per ``TPU_VISIBLE_CHIPS=k`` reading ``/proc/self/fd`` once the backend
        was open: chip k opens ``/dev/vfio/k`` and no other group, k = 0..3,
        whatever the groups' minor numbers are (3,1,0,2 on that machine,
        3,2,0,1 on PR 36's). A chip whose group is not there by that name is
        left out: never wait on a device that may be a neighbour's."""
        if chip_ids is None:
            return sorted(glob.glob("/dev/vfio/[0-9]*"))
        return [p for p in (f"/dev/vfio/{i}" for i in chip_ids) if os.path.exists(p)]

    @staticmethod
    def wait_for_chips(paths: Sequence[str]) -> float:
        """Return, in seconds waited, once every device of ``paths`` can be
        opened. A VFIO group admits one opener, and a worker that died keeps
        its groups until its last thread has left the kernel (``cluster_utils
        .is_gone``), seconds after the controller gave its chips to the next
        one. So open each read-write and close it again, which is what libtpu
        does a moment later and leaves nothing behind, and while that fails
        with EBUSY try again every ``CHIP_POLL_S``. Past ``CHIP_WAIT_BOUND_S``
        raise, naming the device and who holds it."""
        t0 = time.monotonic()
        for path in paths:
            t_path, holder = time.monotonic(), None
            while True:
                try:
                    _open_and_close(path)  # may itself block while the holder lets go
                    break
                except OSError as e:
                    if e.errno != errno.EBUSY:
                        raise
                if holder is None:
                    holder = _holder_of(path)  # while it can still be seen
                waited = time.monotonic() - t0
                if waited > CHIP_WAIT_BOUND_S:
                    raise TimeoutError(
                        f"{path} is still held after {waited:.0f} s, by {_holder_of(path)}: "
                        "this worker was granted the chip and cannot open it"
                    )
                time.sleep(CHIP_POLL_S)
            took = time.monotonic() - t_path
            if took > CHIP_POLL_S:
                logger.warning("waited %.1f s for %s, held by %s", took, path,
                               holder or "no one it met (the open itself took that long)")
        return time.monotonic() - t0

    @staticmethod
    def get_current_process_visible_accelerator_ids() -> Optional[List[int]]:
        raw = os.environ.get(TPU_VISIBLE_CHIPS_ENV)
        if raw is None or raw == "":
            return None
        return [int(x) for x in raw.split(",")]

    # -- pod topology resources --------------------------------------------
    @staticmethod
    def get_current_node_additional_resources() -> Dict[str, float]:
        """Slice-topology resources for this host (reference: tpu.py:334-397).

        Every host of pod slice "v5p-64" gets ``TPU-v5p-64: 1``; host 0
        additionally gets ``TPU-v5p-64-head: 1``. A STRICT_PACK PG on the
        head resource + per-host name resources gang-reserves the slice.
        """
        pod_type = TPUAcceleratorManager.get_current_node_accelerator_type()
        if not pod_type:
            return {}
        out = {f"TPU-{pod_type}": 1.0}
        if TPUAcceleratorManager.get_current_node_tpu_worker_id() == 0:
            out[f"TPU-{pod_type}-head"] = 1.0
        return out

    @staticmethod
    def num_hosts_in_slice(pod_type: str) -> int:
        """e.g. v5p-64 → 64 chips / 4 chips-per-host = 16... chips-per-host
        varies by generation; v5e=8 (1 host unit), v4/v5p=4."""
        try:
            gen, chips = pod_type.split("-")
            chips = int(chips)
        except ValueError:
            return 1
        per_host = 8 if gen in ("v5litepod", "v5e", "v6e") else 4
        return max(1, chips // per_host)
