"""Test configuration.

Mirrors the reference's workhorse pattern of single-process-host multi-node
clusters (reference: python/ray/tests/conftest.py:419 ``ray_start_regular``,
python/ray/cluster_utils.py:135 ``Cluster``): every test runs against a real
multi-process cluster on localhost.

JAX tests run on a virtual 8-device CPU mesh so multi-chip sharding logic is
exercised without TPU hardware (the driver separately dry-runs the multichip
path; see ``__graft_entry__.py``).
"""
import contextlib
import faulthandler
import os
import signal

# Force CPU with 8 virtual devices: tier-1 must not depend on a chip (a
# TPU host exports JAX_PLATFORMS=tpu,cpu). The env writes are a hard
# override and are inherited by worker subprocesses the tests spawn.
os.environ["JAX_PLATFORMS"] = "cpu"
# ... nor on what an earlier run left on disk: the persistent compile
# cache (<repo>/.jax_cache, core/node_agent.place_compile_cache) stays
# off, so compile counts and timings are those of a fresh process.
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

# Runtime lock-order watchdog (RTL005's dynamic sibling): tier-1 runs with
# every ray_tpu-created lock instrumented for order cycles and long holds.
# The module is loaded by file path, pre-seeded into sys.modules under its
# canonical name, BEFORE `import ray_tpu` anywhere — the package __init__
# pulls in the whole core, and locks created during that import must
# already go through the patched factories.
os.environ.setdefault("RAY_TPU_LOCKWATCH", "1")
os.environ.setdefault("RAY_TPU_LOCKWATCH_HOLD_MS", "500")
import importlib.util as _ilu  # noqa: E402
import sys  # noqa: E402

if "ray_tpu.util.lockwatch" not in sys.modules:
    _spec = _ilu.spec_from_file_location(
        "ray_tpu.util.lockwatch",
        os.path.join(
            os.path.dirname(__file__), "..", "ray_tpu", "util", "lockwatch.py"
        ),
    )
    _lockwatch = _ilu.module_from_spec(_spec)
    sys.modules["ray_tpu.util.lockwatch"] = _lockwatch
    _spec.loader.exec_module(_lockwatch)
sys.modules["ray_tpu.util.lockwatch"].maybe_install()

# The env vars above reach a jax imported AFTER this line, here and in
# worker subprocesses. If a plugin imported jax before conftest ran, its
# config already holds the host's platform; backends initialize lazily, so
# flipping the config before first use still switches this process to CPU.
import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import pytest  # noqa: E402

# The one limit a test. The suite's only other clock is the driver's, which
# kills the whole run without a word (PR 55's was cut at 1,473 s of 1,470 by
# one wait nobody could name). Sized from the slowest case under six workers
# (90-114 s, 240 s with a second suite run beside; CHANGES.md, PR 56) and so
# that a whole run with two waits in it still ends inside the driver's
# 1,470 s. No inner bound in tests/ (a `timeout=` of `get`, `subprocess.run`,
# `urlopen`) may stand at or above it.
TEST_LIMIT_S = 300.0
# How long after the limit a worker whose main thread sits in a call that no
# signal interrupts (so the handler never runs) dumps its stacks and exits.
# xdist reports the case as failed ("node down", "worker ... crashed while
# running ...") and, under `--dist loadfile`, hands the file's unfinished
# cases, THAT ONE INCLUDED, to another worker: a wait that comes now and then
# passes there; one that comes every time ends a worker each time.
_EXIT_AFTER_S = 60.0


@contextlib.contextmanager
def time_limit(nodeid, limit_s, exit_s):
    """Hold the body to ``limit_s`` seconds; end the process at ``exit_s``.

    At ``limit_s`` SIGALRM's handler writes every thread's stack to stderr
    and raises pytest's ``Failed`` (a BaseException: no ``except Exception``
    of the code under test swallows it) in the main thread, wherever it
    stands. At ``exit_s`` the interpreter's own watchdog thread writes the
    stacks and calls ``_exit(1)``. Both go to the stderr of the moment the
    body starts (a dup: capture may point fd 2 elsewhere during the body).
    Main thread only; does not nest (the process has one ITIMER_REAL and one
    ``dump_traceback_later``).
    """
    err = os.dup(2)

    def reached(signum, frame):
        faulthandler.dump_traceback(file=err, all_threads=True)
        pytest.fail(
            f"{nodeid} reached the limit of {limit_s:g} s a test "
            "(tests/conftest.py TEST_LIMIT_S; every thread's stack is on stderr)"
        )

    old = signal.signal(signal.SIGALRM, reached)
    faulthandler.dump_traceback_later(exit_s, exit=True, file=err)
    signal.setitimer(signal.ITIMER_REAL, limit_s)
    try:
        yield
    finally:
        signal.setitimer(signal.ITIMER_REAL, 0)
        faulthandler.cancel_dump_traceback_later()
        signal.signal(signal.SIGALRM, old)
        os.close(err)


@pytest.hookimpl(wrapper=True)
def _limited(item):
    # Each phase is armed on its own: a raise BETWEEN two phases would reach
    # pytest as an internal error, and a teardown that follows a test which
    # reached the limit needs time of its own to end the test's cluster
    # (`end_cluster` is bounded: GONE_BOUND_S, core/cluster_utils.py).
    with time_limit(item.nodeid, TEST_LIMIT_S, TEST_LIMIT_S + _EXIT_AFTER_S):
        return (yield)


pytest_runtest_setup = pytest_runtest_call = pytest_runtest_teardown = _limited


# NOTE on numerics: this CPU backend's default matmul runs at reduced
# precision (bf16-class, ~1e-3 relative error). Tests that compare two ways
# of computing the same numbers either use `jax.default_matmul_precision
# ("highest")` locally (slow — avoid around pallas interpret mode) or use
# tolerances sized for the low-precision default.


@pytest.fixture
def ray_start_regular():
    """A running 1-node cluster, torn down after the test."""
    import ray_tpu

    ray_tpu.init(num_cpus=4, resources={"TPU": 4})
    yield ray_tpu
    ray_tpu.shutdown()


def shared_cluster_fixtures(**init_kw):
    """Module-level override for ``ray_start_regular`` that reuses ONE
    cluster across the whole file instead of init/shutdown per test.

    Usage (in a test module)::

        from conftest import shared_cluster_fixtures
        ray_start_regular, _shared_cluster = shared_cluster_fixtures(
            num_cpus=4, resources={"TPU": 4})

    Both names must be module attributes for pytest to collect them. The
    per-test fixture is keep-alive, not scope="module": a test that needs
    its own cluster config may call ``ray_tpu.shutdown()`` and init its
    own (tearing that down again when done) — the NEXT fixture use simply
    re-inits. The module-scoped guard tears the survivor down at file end.
    """
    import ray_tpu  # noqa: F401 — resolved lazily below
    from ray_tpu.core import api as _api

    @pytest.fixture(name="ray_start_regular")
    def _shared(_shared_cluster_guard):
        import ray_tpu

        if _api._global_worker is None:
            ray_tpu.init(**init_kw)
        yield ray_tpu

    @pytest.fixture(scope="module")
    def _shared_cluster_guard():
        yield
        import ray_tpu

        if _api._global_worker is not None:
            ray_tpu.shutdown()

    return _shared, _shared_cluster_guard


@pytest.fixture
def ray_start_cluster():
    """A Cluster object tests can add/remove nodes on (multi-node on one host)."""
    from ray_tpu.core.cluster_utils import Cluster

    cluster = Cluster()
    yield cluster
    cluster.shutdown()
