"""A cluster's end and a chip's release (PR 37).

``cluster_utils.is_gone`` is THE definition of a process being gone,
``cluster_utils.end_cluster`` the one place a cluster is ended, and
``TPUAcceleratorManager.wait_for_chips`` what a worker does before it opens
chips whose last holder may still be dying. No chip here: a device is stood
in for by a file that admits one ``flock``, as a VFIO group admits one opener.
"""
import errno
import fcntl
import os
import platform
import signal
import subprocess
import sys
import textwrap
import time

import pytest

import ray_tpu
from ray_tpu.accelerators import tpu
from ray_tpu.core import api, cluster_utils
from ray_tpu.util import state as state_api

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_SYS_EXIT = {"x86_64": 60, "aarch64": 93}  # exit(2): ends the calling THREAD


def _wait_until(cond, timeout=20.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if cond():
            return True
        time.sleep(0.01)
    return cond()


def _ps_state(pid):
    with open(f"/proc/{pid}/stat") as f:
        return f.read().rsplit(")", 1)[1].split()[0]


# ---------------------------------------------------------------- (a)
_LEADER_LEAVES = textwrap.dedent("""
    import ctypes, fcntl, os, sys, threading, time
    lock, hold_s, sys_exit = sys.argv[1], float(sys.argv[2]), int(sys.argv[3])
    fd = os.open(lock, os.O_RDWR | os.O_CREAT)
    fcntl.flock(fd, fcntl.LOCK_EX)
    def last():
        time.sleep(hold_s)
        os._exit(0)
    if hold_s:
        threading.Thread(target=last).start()
        ctypes.CDLL(None).syscall(sys_exit, 0)  # the leader alone leaves
    os._exit(0)
""")


@pytest.mark.parametrize("hold_s", [1.0, 0.0], ids=["zombie_leader_with_a_thread", "plain_zombie"])
def test_gone_is_no_entry_or_a_zombie_with_one_thread(tmp_path, hold_s):
    if platform.machine() not in _SYS_EXIT:
        pytest.skip("thread-exit syscall number not known for this machine")
    lock = str(tmp_path / "lock")
    child = subprocess.Popen([sys.executable, "-S", "-c", _LEADER_LEAVES, lock,
                              str(hold_s), str(_SYS_EXIT[platform.machine()])])
    start = 0
    try:
        assert _wait_until(lambda: _ps_state(child.pid) == "Z")
        start = cluster_utils.process_start(child.pid)
        if hold_s:
            # a Zl: empty cmdline, and its thread still holds the lock file
            assert open(f"/proc/{child.pid}/cmdline", "rb").read() == b""
            fd = os.open(lock, os.O_RDWR)
            with pytest.raises(BlockingIOError):
                fcntl.flock(fd, fcntl.LOCK_EX | fcntl.LOCK_NB)
            assert not cluster_utils.is_gone(child.pid)
            assert not cluster_utils.is_gone(child.pid, start)
            # ... which the host-wide scan sees as well (same process group)
            assert child.pid in cluster_utils.cluster_processes()
            fcntl.flock(fd, fcntl.LOCK_EX)  # returns when the thread has ended
            os.close(fd)
            assert _wait_until(lambda: cluster_utils.is_gone(child.pid), 5)
        # a plain unreaped zombie holds nothing: gone, though /proc lists it
        assert _ps_state(child.pid) == "Z"
        assert cluster_utils.is_gone(child.pid)
        assert cluster_utils.is_gone(child.pid, start)
        assert child.pid not in cluster_utils.cluster_processes()
        # the pid under another start time is another process: gone
        assert cluster_utils.is_gone(child.pid, start + 1)
    finally:
        child.wait(timeout=10)
    assert cluster_utils.is_gone(child.pid) and cluster_utils.process_start(child.pid) == 0


# ---------------------------------------------------------------- (b)
def _named_by_the_controller(monkeypatch):
    """Record what ``shutdown_cluster`` answers to the next ``end_cluster``."""
    named = []
    real = api._global_worker._call

    def spy(method, *a, **kw):
        out = real(method, *a, **kw)
        if method == "shutdown_cluster":
            named.extend(out)
        return out

    monkeypatch.setattr(api._global_worker, "_call", spy)
    return named


_OTHER_CLUSTER = textwrap.dedent("""
    import sys
    import ray_tpu
    from ray_tpu.core import api
    ray_tpu.init(num_cpus=1)
    print(api._controller_proc.pid, flush=True)
    sys.stdin.read()
    ray_tpu.shutdown()
""")


@pytest.mark.parametrize("neighbour", [False, True], ids=["alone", "beside_another_cluster"])
def test_shutdown_returns_when_its_own_cluster_is_gone(monkeypatch, neighbour):
    other = None
    if neighbour:
        other = subprocess.Popen([sys.executable, "-c", _OTHER_CLUSTER], stdin=subprocess.PIPE,
                                 stdout=subprocess.PIPE, text=True, cwd=REPO)
        their_controller = int(other.stdout.readline())
    try:
        ray_tpu.init(num_cpus=2)

        @ray_tpu.remote
        class A:
            def pid(self):
                return os.getpid()

        actor_pid = ray_tpu.get(A.remote().pid.remote(), timeout=120)
        controller_pid = api._controller_proc.pid
        named = _named_by_the_controller(monkeypatch)
        t0 = time.monotonic()
        ray_tpu.shutdown()
        took = time.monotonic() - t0
        pids = [pid for pid, _ in named]
        assert actor_pid in pids and controller_pid in pids and len(pids) >= 3
        assert all(start > 0 for _, start in named)
        left = [pid for pid, start in named if not cluster_utils.is_gone(pid, start)]
        assert not left, left
        assert took < 3.0, f"shutdown() of a CPU cluster took {took:.2f} s"
        if neighbour:
            # the neighbour's cluster is still there, and nobody waited for it
            assert their_controller in cluster_utils.cluster_processes()
            assert their_controller not in pids
        else:
            assert not set(pids) & set(cluster_utils.cluster_processes())
    finally:
        ray_tpu.shutdown()
        if other is not None:
            other.stdin.close()
            other.wait(timeout=30)


# ---------------------------------------------------------------- (c)
def _busy_n_times(n):
    calls = []

    def opener(path):
        calls.append(path)
        if len(calls) <= n:
            raise OSError(errno.EBUSY, "Device or resource busy", path)

    return opener, calls


@pytest.mark.parametrize("busy", [0, 1, 7])
def test_wait_for_chips_tries_again_while_the_device_is_busy(monkeypatch, tmp_path, caplog, busy):
    opener, calls = _busy_n_times(busy)
    monkeypatch.setattr(tpu, "_open_and_close", opener)
    monkeypatch.setattr(tpu, "CHIP_POLL_S", 0.01)
    dev = str(tmp_path / "vfio0")
    with caplog.at_level("WARNING", logger="ray_tpu.tpu"):
        waited = tpu.TPUAcceleratorManager.wait_for_chips([dev])
    assert calls == [dev] * (busy + 1)
    assert waited >= busy * 0.01
    said = [r.getMessage() for r in caplog.records if "waited" in r.getMessage()]
    assert len(said) == (1 if busy else 0), said  # one line, and only for a real wait
    if busy:
        assert dev in said[0] and "held by" in said[0]


def test_wait_for_chips_opens_the_real_file_and_passes_other_errors_on(tmp_path):
    dev = tmp_path / "vfio0"
    with pytest.raises(FileNotFoundError):
        tpu.TPUAcceleratorManager.wait_for_chips([str(dev)])
    dev.write_bytes(b"")
    assert tpu.TPUAcceleratorManager.wait_for_chips([str(dev)]) < 1.0
    assert tpu.TPUAcceleratorManager.wait_for_chips([]) < 1.0
    # no VFIO group on this host: nothing to wait for, whatever was granted
    if not os.path.exists("/dev/vfio"):
        assert tpu.TPUAcceleratorManager.chip_devices(None) == []
        assert tpu.TPUAcceleratorManager.chip_devices([0, 1]) == []


def test_wait_for_chips_past_its_bound_names_the_device_and_its_holder(monkeypatch, tmp_path):
    dev = str(tmp_path / "vfio0")
    open(dev, "w").close()
    opener, calls = _busy_n_times(10**9)
    monkeypatch.setattr(tpu, "_open_and_close", opener)
    monkeypatch.setattr(tpu, "CHIP_POLL_S", 0.01)
    monkeypatch.setattr(tpu, "CHIP_WAIT_BOUND_S", 0.2)
    with open(dev) as f:
        holder = subprocess.Popen(["sleep", "30"], stdin=f)
    try:
        t0 = time.monotonic()
        with pytest.raises(TimeoutError) as e:
            tpu.TPUAcceleratorManager.wait_for_chips([dev])
        assert 0.2 <= time.monotonic() - t0 < 5.0
        assert dev in str(e.value) and f"pid {holder.pid}" in str(e.value), str(e.value)
        assert len(calls) > 5
    finally:
        holder.kill()
        holder.wait()


# ---------------------------------------------------------------- (d)
# Every process of the cluster started below imports this first (PYTHONPATH):
# the granted chip is a file, and opening it is taking its flock.
_STAND_IN = textwrap.dedent("""
    import errno, fcntl, os
    import ray_tpu.accelerators.tpu as tpu

    HERE = os.path.dirname(os.path.abspath(__file__))

    def _open_and_close(path):
        fd = os.open(path, os.O_RDWR)
        try:
            fcntl.flock(fd, fcntl.LOCK_EX | fcntl.LOCK_NB)
        except BlockingIOError:
            open(os.path.join(HERE, "seen_busy"), "w").close()
            raise OSError(errno.EBUSY, "Device or resource busy", path) from None
        finally:
            os.close(fd)

    tpu._open_and_close = _open_and_close
    tpu.TPUAcceleratorManager.chip_devices = staticmethod(
        lambda chip_ids: [os.path.join(HERE, "chip0")])
""")


def test_restarted_tpu_actor_waits_for_its_dying_predecessor(monkeypatch, tmp_path):
    (tmp_path / "sitecustomize.py").write_text(_STAND_IN)
    chip, let_go = str(tmp_path / "chip0"), str(tmp_path / "let_go")
    open(chip, "w").close()
    monkeypatch.setenv("PYTHONPATH", str(tmp_path) + os.pathsep + os.environ.get("PYTHONPATH", ""))
    ray_tpu.init(num_cpus=2, resources={"TPU": 1})
    try:

        @ray_tpu.remote(resources={"TPU": 1}, max_restarts=1)
        class OnTheChip:
            def __init__(self, chip, let_go):
                # libtpu: one opener, and no second try
                self.fd = os.open(chip, os.O_RDWR)
                try:
                    fcntl.flock(self.fd, fcntl.LOCK_EX | fcntl.LOCK_NB)
                except BlockingIOError:
                    raise RuntimeError(f"open({chip}): Device or resource busy") from None
                # the kernel: the device outlives the process that held it
                # (`cat` ends when this process does, whatever way it goes),
                # here until the test has seen the next worker wait for it
                r, self.w = os.pipe()
                subprocess.Popen(
                    ["sh", "-c", f"cat >/dev/null; until [ -e {let_go} ]; do sleep 0.05; done"],
                    stdin=r, pass_fds=(self.fd,))
                os.close(r)

            def pid(self):
                return os.getpid()

        def chip_waits():
            return [e["chip_wait_ms"] for e in state_api.list_lifecycle_events(limit=100000)
                    if e.get("kind") == "worker" and e.get("state") == "CHIPS_READY"]

        a = OnTheChip.remote(chip, let_go)
        first = ray_tpu.get(a.pid.remote(), timeout=60)
        # the chip was free: the wait is in the worker's records, and is none
        assert _wait_until(lambda: len(chip_waits()) == 1), chip_waits()
        assert chip_waits()[0] < 1000
        os.kill(first, signal.SIGKILL)
        assert _wait_until(lambda: os.path.exists(tmp_path / "seen_busy"), 60)
        open(let_go, "w").close()
        second = None
        deadline = time.monotonic() + 60
        while second is None:
            try:
                second = ray_tpu.get(a.pid.remote(), timeout=60)
            except ray_tpu.exceptions.ActorDiedError:
                # the call that found the first one dead; a creation that failed
                # (the parent's: "Device or resource busy") never gets past here
                assert time.monotonic() < deadline
                time.sleep(0.05)
        assert second != first
        assert _wait_until(lambda: len(chip_waits()) == 2), chip_waits()
        assert chip_waits()[1] >= tpu.CHIP_POLL_S * 1000, chip_waits()
    finally:
        open(let_go, "w").close()
        ray_tpu.shutdown()
