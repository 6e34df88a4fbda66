"""What only the process that holds the chip can do: look at the device,
trace it, and reduce the trace. The ``JaxTrainer`` worker and the ``serve``
replica call these; the benchmark's own process never touches JAX.
"""
from __future__ import annotations

import os
import shutil
import tempfile
import time


def require_device(rehearse: bool):
    """JAX's first device. Without ``--rehearse`` anything but a TPU is an
    error: a measurement never falls back to the CPU."""
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu" and not rehearse:
        raise RuntimeError(
            f"chipbench: JAX found no accelerator: platform {dev.platform!r} ({dev.device_kind}), "
            f"JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS')!r}. A cell is measured on the chip only.")
    return dev


def device_facts() -> dict:
    """The ``device`` object of the last line, as JAX reports it here."""
    import jax

    devs = jax.local_devices()
    peaks = []
    for d in devs:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    if not max(peaks):
        # The CPU backend keeps no such count: the rehearsal reports the
        # process's own peak so that the line has the contract's shape.
        import resource

        peaks = [resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024]
    return {"platform": devs[0].platform, "kind": devs[0].device_kind, "count": len(devs),
            "memory_peak_bytes": max(peaks)}


def compile_count() -> int:
    """Compilations this process has made so far (``compile_tracker``)."""
    from ray_tpu.util import compile_tracker

    compile_tracker.install()
    return int(compile_tracker.snapshot()["compiles"])


def compiled_functions() -> dict:
    """Compilations so far by function name, to say which one a window held."""
    from ray_tpu.util import compile_tracker

    return {k: v["count"] for k, v in compile_tracker.snapshot(max_functions=10_000)["functions"].items()}


class DeviceTrace:
    """One ``jax.profiler`` trace of this process, reduced where it was taken.
    The Python tracer stays off: it slows the host thread it watches."""

    def __init__(self):
        self.dir = tempfile.mkdtemp(prefix="chipbench_trace_")
        self.t0 = None

    def start(self):
        import jax

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self.t0 = time.time()

    def stop(self, keep_to: str = "") -> dict:
        import jax

        from chipbench import trace_reduce

        t1 = time.time()
        jax.profiler.stop_trace()
        try:
            path = trace_reduce.find_xplane(self.dir)
            reduced = trace_reduce.reduce_planes(trace_reduce.read_xplane(path))
            if keep_to:
                os.makedirs(os.path.dirname(keep_to), exist_ok=True)
                shutil.copy(path, keep_to)
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)
        reduced["host_t0"], reduced["host_t1"] = self.t0, t1
        return reduced
