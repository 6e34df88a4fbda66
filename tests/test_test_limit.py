"""The limit a test (``conftest.time_limit``, armed by conftest's hooks with
``TEST_LIMIT_S``): a body that waits fails by name with every thread's stack
on stderr, a body that ends leaves nothing armed, and a main thread that no
signal reaches ends its process at the second bound."""
import os
import signal
import subprocess
import sys
import threading
import time

import pytest

from conftest import TEST_LIMIT_S, time_limit

TESTS = os.path.dirname(os.path.abspath(__file__))


def _armed():
    left, interval = signal.getitimer(signal.ITIMER_REAL)
    return 0 < left <= TEST_LIMIT_S and interval == 0


@pytest.fixture
def armed_in_setup_and_teardown():
    assert _armed()
    yield
    assert _armed()


def test_the_hooks_arm_the_limit_around_setup_call_and_teardown(armed_in_setup_and_teardown):
    assert _armed()


def test_a_body_that_waits_fails_by_name_with_the_stacks_on_stderr(capfd):
    def the_frame_that_waited():
        threading.Event().wait()

    t0 = time.monotonic()
    with pytest.raises(pytest.fail.Exception) as failed:
        with time_limit("tests/x.py::test_that_waits[case]", 0.5, 30):
            the_frame_that_waited()
    assert time.monotonic() - t0 < 3
    assert "tests/x.py::test_that_waits[case]" in str(failed.value)
    assert "limit of 0.5 s" in str(failed.value)
    err = capfd.readouterr().err
    assert "the_frame_that_waited" in err and "most recent call first" in err
    assert signal.getitimer(signal.ITIMER_REAL) == (0.0, 0.0)


def _child(body, bound_s):
    code = f"import sys; sys.path.insert(0, {TESTS!r})\nfrom conftest import time_limit\n{body}"
    return subprocess.run([sys.executable, "-c", code], capture_output=True, text=True,
                          timeout=bound_s)


def test_a_body_that_ends_leaves_no_timer_armed_and_no_dump_pending():
    r = _child(
        "import signal, time\n"
        "with time_limit('x', 5, 0.5):\n"
        "    pass\n"
        "assert signal.getitimer(signal.ITIMER_REAL) == (0.0, 0.0)\n"
        "assert signal.getsignal(signal.SIGALRM) is signal.SIG_DFL\n"
        "time.sleep(1.0)\n"  # past the second bound: a dump still pending would exit 1
        "print('still here')\n",
        bound_s=120,
    )
    assert r.returncode == 0 and "still here" in r.stdout, (r.returncode, r.stderr[-2000:])
    assert "most recent call first" not in r.stderr


def test_a_main_thread_no_signal_reaches_ends_its_process_at_the_second_bound():
    t0 = time.monotonic()
    r = _child(
        "import signal, threading, time\n"
        "def beside():\n"
        "    time.sleep(60)\n"
        "threading.Thread(target=beside, daemon=True).start()\n"
        "signal.pthread_sigmask(signal.SIG_BLOCK, {signal.SIGALRM})\n"
        "def blocked():\n"
        "    with time_limit('x', 0.3, 1.5):\n"
        "        time.sleep(60)\n"
        "t0 = time.monotonic()\n"
        "try:\n"
        "    blocked()\n"
        "finally:\n"
        "    print('left after', time.monotonic() - t0)\n",
        bound_s=120,
    )
    assert time.monotonic() - t0 < 60  # importing conftest (jax) is most of it; the body sleeps 60
    assert r.returncode != 0 and "left after" not in r.stdout, (r.returncode, r.stdout)
    assert "Timeout (0:00:01.5" in r.stderr, r.stderr[-2000:]
    # every thread's stack, not the main one's alone
    assert "in blocked" in r.stderr and "in beside" in r.stderr, r.stderr[-2000:]
