"""Test configuration: run JAX on a virtual 8-device CPU mesh.

Mirrors the reference's multi-backend test strategy (SURVEY.md §4): the
numerics tests run identically on CPU and TPU; sharding tests get 8
virtual devices via XLA's host-platform device-count flag. Both are
set in the environment before JAX is imported.
"""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()
os.environ.setdefault("VELES_TPU_BACKEND", "cpu")

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402

assert jax.default_backend() == "cpu", jax.default_backend()


# -- per-test watchdog (the reference's tests/timeout.py:36-60 role) --------
#
# A wedged test (deadlocked coordinator thread, stuck subprocess) must
# fail loudly with stacks, not hang CI. The watchdog interrupts the
# main thread after VELES_TEST_TIMEOUT seconds (default 600).

import faulthandler  # noqa: E402
import threading  # noqa: E402
import _thread  # noqa: E402

import pytest  # noqa: E402

_TEST_TIMEOUT = float(os.environ.get("VELES_TEST_TIMEOUT", 600))


def pytest_configure(config):
    # the tier-1 job runs -m 'not slow'; long soaks opt out with it
    config.addinivalue_line(
        "markers", "slow: long-running tests excluded from tier-1")


@pytest.fixture(autouse=True)
def _test_watchdog():
    if _TEST_TIMEOUT <= 0:
        yield
        return
    fired = threading.Event()

    def trip():
        fired.set()
        sys.stderr.write(
            "\n[watchdog] test exceeded %.0fs — thread stacks follow\n"
            % _TEST_TIMEOUT)
        faulthandler.dump_traceback()
        _thread.interrupt_main()

    timer = threading.Timer(_TEST_TIMEOUT, trip)
    timer.daemon = True
    timer.start()
    try:
        yield
        if fired.is_set():
            pytest.fail("test exceeded the %.0fs watchdog" % _TEST_TIMEOUT)
    finally:
        timer.cancel()


# -- telemetry singleton isolation ------------------------------------------
#
# The profiler layer owns process-singleton daemon threads (the flight
# recorder's stall watchdog, the HBM/RSS sampler). Tests that touched
# them must not leak live threads into interpreter shutdown — the
# C++ runtimes under jax/zmq tear down their own state at exit, and a
# watcher thread still polling through that window intermittently
# dies with "terminate called without an active exception". Joining
# the threads (and detaching the recorder's root-logger handler)
# before pytest exits removes the window.

@pytest.fixture(autouse=True, scope="session")
def _stop_telemetry_threads():
    yield
    # prefetch pipelines first: their workers hold jax arrays, and a
    # worker mid-device_put through interpreter teardown is the same
    # "terminate called without an active exception" window
    from veles_tpu.train import offload
    offload.shutdown_all()
    from veles_tpu.loader import prefetch
    prefetch.shutdown_all()
    from veles_tpu.telemetry import alerts, flight, profiler
    alerts.reset_engine()
    flight.reset_recorder()
    profiler.stop_memory_sampler()
