"""Test configuration: force an 8-device virtual CPU platform so compiled
multi-chip collectives and shardings run without TPU hardware (the strategy
SURVEY.md §4 prescribes: a cheap real backend on localhost, like the
reference's Gloo-on-TCP-loopback)."""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import pytest  # noqa: E402

# ---------------------------------------------------------------------------
# Hang forensics.  A suite that wedges (a deadlocked subprocess test, a
# stuck collective) used to die as a bare `timeout -k` kill with no
# evidence.  Arm faulthandler's watchdog just under the tier-1 budget
# (the driver's verify runs under `timeout -k 10 870`, so default 850 s):
# if the run is still going then, every thread's stack is dumped to
# stderr — the run keeps going (exit=False); only the external timeout
# kills it, now with a post-mortem attached.  ci/run_test_tiers.sh sets
# HVD_TPU_CI_HANG_DUMP_S per tier; 0 disables.
# ---------------------------------------------------------------------------

import faulthandler  # noqa: E402

_HANG_DUMP_S = int(os.environ.get("HVD_TPU_CI_HANG_DUMP_S", "850") or 0)
if _HANG_DUMP_S > 0:
    faulthandler.enable()
    faulthandler.dump_traceback_later(_HANG_DUMP_S, exit=False)


@pytest.fixture(autouse=True)
def _fresh_runtime():
    """Each test starts uninitialized (init() is idempotent; tests that call
    init() get a clean shutdown afterwards)."""
    yield
    import horovod_tpu as hvd
    if hvd.is_initialized():
        hvd.shutdown()


@pytest.fixture(autouse=True)
def _fresh_recovery_tier():
    """The replica store and chaos schedule are process-global (one job
    per process in production); between tests they are state leaks —
    a sealed replica from one test must not win a later test's peer
    restore.  Lazy: tests that never touched recovery pay nothing."""
    yield
    import sys as _sys
    mod = _sys.modules.get("horovod_tpu.recovery")
    if mod is not None:
        mod.reset_store()
        mod.reset_chaos()


@pytest.fixture
def interpreted_kernels(monkeypatch):
    """Off the chip the dispatch takes the XLA branch and a Mosaic kernel
    cannot run: ask for the kernels and run them in the Pallas interpreter.
    Steering in the test, no option of the program."""
    from horovod_tpu.ops import flash_attention as fa
    monkeypatch.setenv("HVD_TPU_FLASH", "1")
    real = fa.flash_attention
    monkeypatch.setattr(
        fa, "flash_attention",
        lambda *a, **kw: real(*a, **kw, interpret=True))


@pytest.fixture(scope="session", autouse=True)
def _no_stray_background_threads():
    """No non-daemon background thread started during the suite may
    survive it: a leaked worker (a prefetch producer whose close() was
    skipped, an autotune helper, a wedged controller loop) would hang
    the interpreter at exit — in CI that reads as a timeout with no
    traceback.  Threads alive before the session (pytest/plugin
    machinery) are exempt; stragglers get a short grace join first so
    a thread mid-teardown does not flake the whole run."""
    import threading
    # Thread OBJECTS, not idents: idents are recycled by the OS, and a
    # held reference is what guarantees no identity reuse.
    before = set(threading.enumerate())
    yield
    leaked = [t for t in threading.enumerate()
              if t.is_alive() and t not in before
              and t is not threading.main_thread()
              # All non-daemon stragglers, PLUS this framework's own
              # daemon workers (prefetch producers are daemonized so a
              # crash can't hang the interpreter — but a LEAKED one
              # still means a close() was skipped; catch it by name).
              and (not t.daemon or t.name.startswith("hvd-tpu-"))]
    for t in leaked:
        t.join(timeout=5)
    leaked = [t for t in leaked if t.is_alive()]
    assert not leaked, (
        "background threads survived the test session (skipped close()/"
        f"join, interpreter exit may hang): {[t.name for t in leaked]}")


# ---------------------------------------------------------------------------
# Timeout enforcement.  pytest-timeout is not installed in this image, so
# @pytest.mark.timeout marks would silently be no-ops; enforce them (plus a
# default ceiling for unmarked tests) with SIGALRM so a wedged subprocess
# test fails loudly instead of hanging the whole suite.
# ---------------------------------------------------------------------------

import signal  # noqa: E402
import threading  # noqa: E402

_DEFAULT_TEST_TIMEOUT = int(os.environ.get("HVD_TPU_TEST_TIMEOUT", "180"))


def _alarm_guard(item, phase, default_seconds=None):
    marker = item.get_closest_marker("timeout")
    seconds = int(marker.args[0]) if marker and marker.args \
        else (default_seconds or _DEFAULT_TEST_TIMEOUT)

    def _on_alarm(signum, frame):
        raise TimeoutError(
            f"{phase} exceeded {seconds}s timeout "
            "(conftest SIGALRM enforcer)")

    use_alarm = threading.current_thread() is threading.main_thread()
    if use_alarm:
        old = signal.signal(signal.SIGALRM, _on_alarm)
        signal.alarm(seconds)
    return use_alarm, (old if use_alarm else None)


def _alarm_clear(use_alarm, old):
    if use_alarm:
        signal.alarm(0)
        signal.signal(signal.SIGALRM, old)


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_call(item):
    use_alarm, old = _alarm_guard(item, "test")
    try:
        yield
    finally:
        _alarm_clear(use_alarm, old)


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_setup(item):
    use_alarm, old = _alarm_guard(item, "setup")
    try:
        yield
    finally:
        _alarm_clear(use_alarm, old)


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_teardown(item):
    # Teardown (e.g. the _fresh_runtime shutdown) must not wedge the suite
    # either; a stuck controller shutdown fails the test instead.
    use_alarm, old = _alarm_guard(item, "teardown", default_seconds=120)
    try:
        yield
    finally:
        _alarm_clear(use_alarm, old)


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "timeout(seconds): per-test wall-clock limit "
        "(enforced by conftest SIGALRM)")
    config.addinivalue_line(
        "markers", "slow: multi-minute performance/regression tests "
        "(deselect with -m 'not slow')")
