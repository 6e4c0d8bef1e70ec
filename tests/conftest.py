"""Test configuration: force an 8-device virtual CPU mesh before jax imports.

Mirrors the reference's "multi-node without a cluster" testing stance
(reference: 3rdparty/ps-lite/tests/local.sh runs schedulers/servers/workers
as localhost processes): unit tests run single-process, state-machine tests
use a fake in-process transport, integration tests spawn real subprocesses.
"""

import faulthandler
import gc
import os
import signal
import sys

import pytest

os.environ["JAX_PLATFORMS"] = "cpu"
# LAPACK calls from jitted code (lax.linalg.triangular_solve on the CPU)
# run on scipy's OpenBLAS, whose pool threads spin on every core between
# calls: beside six xdist workers that starves the chaos tests' 0.2 s
# heartbeats. One thread a process is plenty at the tests' sizes.
os.environ.setdefault("OPENBLAS_NUM_THREADS", "1")
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# Seconds a test may run unless it carries @pytest.mark.time_limit(n).
# The slowest tier-1 test takes about 30 s on a loaded box; the driver
# gives the whole run 1470 s.
TIME_LIMIT_S = 120.0


@pytest.fixture(autouse=True)
def _thawed_heap():
    """``DeviceResidentTrainer.warmup`` settles the heap
    (``runtime.settle_heap``: a collection, then ``gc.freeze()``), which
    is right for a job and wrong for a worker of the suite that goes on
    to a thousand other tests: what a test froze goes back to the
    collector when the test is over."""
    yield
    gc.unfreeze()


class TimeLimitExceeded(Exception):
    """Raised in the main thread of a test that outran its time limit."""


@pytest.fixture(autouse=True)
def _time_limit(request):
    """Fail a test that runs too long, under its own name, and go on.

    At the limit every thread's stack goes to stderr (faulthandler) and
    SIGALRM raises in the main thread, where the test body runs; what it
    holds is released by its own ``finally`` / context managers. Needs
    no plugin: the driver's command line is fixed and pytest-timeout is
    not installed.
    """
    marker = request.node.get_closest_marker("time_limit")
    limit = float(marker.args[0]) if marker else TIME_LIMIT_S

    def on_alarm(signum, frame):
        raise TimeLimitExceeded(
            f"{request.node.nodeid} still running after {limit:g}s "
            f"(all thread stacks are on stderr)")

    previous = signal.signal(signal.SIGALRM, on_alarm)
    # the dump fires first, from faulthandler's own watchdog thread, so
    # the stacks show where the test stood and not the handler
    faulthandler.dump_traceback_later(max(limit - 0.5, 0.1), exit=False)
    signal.setitimer(signal.ITIMER_REAL, limit)
    try:
        yield
    finally:
        signal.setitimer(signal.ITIMER_REAL, 0)
        faulthandler.cancel_dump_traceback_later()
        signal.signal(signal.SIGALRM, previous)
