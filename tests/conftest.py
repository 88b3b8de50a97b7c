import faulthandler
import os
import signal
import subprocess
import sys
import threading

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SRC = os.path.join(REPO, "src")
if SRC not in sys.path:
    sys.path.insert(0, SRC)

# One BLAS thread per test process.  Tier-1 runs several pytest-xdist
# workers on the host's cores, and OpenBLAS's own pool in each of them
# spin-waits: six workers running the event engine's small SVDs at once
# ran each about 800 times slower than one thread does.  A pytest plugin
# loads numpy before this file, so OPENBLAS_NUM_THREADS would come too
# late; threadpoolctl sets the loaded libraries' pools instead.  JAX's
# CPU eigh and svd call scipy's own OpenBLAS, loaded here first so that
# the limit covers it too.
try:
    from threadpoolctl import threadpool_limits
except ModuleNotFoundError:  # the suite runs without it, only slower
    pass
else:
    import scipy.linalg  # noqa: F401

    threadpool_limits(limits=1, user_api="blas")


# Above ``run_multidevice``'s subprocess timeout, so a hung subprocess
# reports its own timeout first.
TEST_TIME_LIMIT_S = 480


class TimeLimitExceeded(Exception):
    """One test ran past ``TEST_TIME_LIMIT_S``."""


@pytest.fixture(autouse=True)
def _time_limit(request):
    """Fail a test that runs past ``TEST_TIME_LIMIT_S``, with its stack.

    ``faulthandler`` dumps every thread's stack even when the main thread
    is stuck in native code; the alarm then fails the test itself, so a
    hang costs one test instead of the whole run.
    """
    on_main = threading.current_thread() is threading.main_thread()
    faulthandler.dump_traceback_later(TEST_TIME_LIMIT_S, exit=False)
    if on_main:

        def expire(signum, frame):
            raise TimeLimitExceeded(
                f"{request.node.nodeid} ran past {TEST_TIME_LIMIT_S} s"
            )

        previous = signal.signal(signal.SIGALRM, expire)
        signal.alarm(TEST_TIME_LIMIT_S)
    try:
        yield
    finally:
        faulthandler.cancel_dump_traceback_later()
        if on_main:
            signal.alarm(0)
            signal.signal(signal.SIGALRM, previous)


@pytest.fixture
def rng():
    return np.random.default_rng(0)


def run_property(check, *, given, cases, max_examples=100, examples=()):
    """Run a property check under hypothesis, or over a seeded sweep.

    The tier-1 suite must exercise its property tests on minimal installs
    too (hypothesis is an extra, not a requirement), so every property
    test supplies both halves and runs the SAME ``check`` either way:

    given:        zero-arg callable returning the ``hypothesis.given``
                  strategy dict.  Lazy on purpose — strategies cannot be
                  built when hypothesis is absent.  Pass ``None`` when the
                  case family has no natural strategy encoding (e.g. a
                  coupled random construction): the seeded sweep then runs
                  even when hypothesis is installed.
    cases:        iterable of kwargs dicts — the deterministic fallback
                  sweep (seeded numpy, so failures reproduce exactly).
    max_examples: hypothesis example budget (ignored by the fallback).
    examples:     kwargs dicts pinned as cases that run every time — known
                  falsifying examples.  They run as ``hypothesis.example``
                  entries and ahead of the seeded sweep.

    Hypothesis draws derandomized examples and keeps no example database,
    so every run checks the same examples and counts the same.
    """
    try:
        import hypothesis
    except ModuleNotFoundError:
        hypothesis = None
    if hypothesis is not None and given is not None:
        wrapped = hypothesis.given(**given())(check)
        for kw in examples:
            wrapped = hypothesis.example(**kw)(wrapped)
        hypothesis.settings(
            max_examples=max_examples, deadline=None, derandomize=True, database=None
        )(wrapped)()
        return
    ran = 0
    for kw in [*examples, *cases]:
        check(**kw)
        ran += 1
    assert ran > 0, "seeded fallback produced no cases"


def run_multidevice(script: str, n_devices: int = 8, timeout: int = 420) -> str:
    """Run a python snippet in a subprocess with n fake CPU devices.

    Multi-device tests must not pollute this process (smoke tests and
    benches are required to see exactly 1 device), so shard_map/mesh tests
    execute out-of-process.
    """
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n_devices}"
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run(
        [sys.executable, "-c", script],
        capture_output=True,
        text=True,
        timeout=timeout,
        env=env,
    )
    assert proc.returncode == 0, f"subprocess failed:\nSTDOUT:{proc.stdout}\nSTDERR:{proc.stderr}"
    return proc.stdout
