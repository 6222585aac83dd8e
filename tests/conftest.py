import contextlib
import os
import signal
import tempfile
import time

import pytest

import ray_tpu
from ray_tpu._private import stacks

#: Seconds that each of a test's setup, call and teardown may take: about
#: three times the slowest honest test beside five busy xdist workers
#: (test_dreamerv3_cartpole_learns_in_imagination, 78 s alone).
TEST_TIME_LIMIT_S = 300


@contextlib.contextmanager
def _time_limit(seconds, what):
    """Fail whatever runs inside, in this (the main) thread, once it has
    taken ``seconds``: SIGALRM raises ``pytest.fail`` into it, with the
    stack of every thread of this process in the message, and again every
    ``seconds`` for as long as the clean-up it unwinds into takes. On the
    way out the handler found on the way in is put back, and its timer
    with what is left of it (the time spent in here taken off), so
    limits nest and an inner one does not extend the outer.

    A signal is handled between two bytecodes of the main thread. Waits
    on locks, sockets and sleeps are woken for it; a main thread inside
    native code that does not return (a compile, a spinning kernel) is
    not, and the stacks are only of this process, not of the heads,
    raylets and workers it started. For those the backstop is the limit
    on the whole run (``timeout -k 10 1470`` in the driver's command)."""

    def on_alarm(signum, frame):
        # Not faulthandler's dump: it stops after 100 threads, newest
        # first, and an xdist worker late in a run holds more than that,
        # the main thread last.
        pytest.fail(
            f"{what} took more than {seconds:g} s (the limit of "
            f"tests/conftest.py). Every thread of this process:\n"
            f"{stacks.format_all()}"
        )

    handler = signal.signal(signal.SIGALRM, on_alarm)
    entered = time.monotonic()
    left, again = signal.setitimer(signal.ITIMER_REAL, seconds, seconds)
    try:
        yield
    finally:
        signal.setitimer(signal.ITIMER_REAL, 0)
        signal.signal(signal.SIGALRM, handler)
        if left:  # 0 is no timer; an outer one that is due fires at once
            left = max(left - (time.monotonic() - entered), 1e-3)
        signal.setitimer(signal.ITIMER_REAL, left, again)


def _limited(when):
    @pytest.hookimpl(wrapper=True)
    def hook(item):
        with _time_limit(TEST_TIME_LIMIT_S, f"{when} of {item.nodeid}"):
            return (yield)

    return hook


# One hang is one failed test with a name and a stack, not the run's clock.
pytest_runtest_setup = _limited("setup")
pytest_runtest_call = _limited("call")
pytest_runtest_teardown = _limited("teardown")


@pytest.fixture
def time_limit():
    """The limit's context manager, for the tests of the limit itself."""
    return _time_limit


def pytest_configure(config):
    # With the witness armed, point every process — this one and the
    # spawned heads/raylets/workers, via env inheritance — at ONE
    # sidecar violations file. sessionfinish scans it, so an inversion
    # witnessed inside a daemon fails the run too; violations() alone
    # only ever sees the driver process.
    from ray_tpu._private import lock_witness

    if lock_witness.enabled() and not os.environ.get(
        lock_witness.FILE_ENV
    ):
        path = os.path.join(
            tempfile.gettempdir(),
            f"rtpu_lock_witness_{os.getpid()}.log",
        )
        try:
            os.unlink(path)
        except OSError:
            pass
        os.environ[lock_witness.FILE_ENV] = path


def pytest_sessionfinish(session, exitstatus):
    """With the lock witness armed (make race-smoke), a suite that ran
    green but witnessed a lock-order inversion still FAILS — the
    violation is a deadlock waiting for production traffic to align."""
    from ray_tpu._private import lock_witness

    if lock_witness.installed():
        vs = lock_witness.violations()
        rep = lock_witness.witness_report()
        print(f"\n[lock-witness] {rep}")
        side = os.environ.get(lock_witness.FILE_ENV)
        side_text = ""
        if side and os.path.exists(side):
            with open(side, encoding="utf-8") as f:
                side_text = f.read().strip()
            try:
                os.unlink(side)  # consumed: don't leak one per run
            except OSError:
                pass
        if vs or side_text:
            if side_text:
                # The sidecar already holds this process's findings
                # (pid-tagged) alongside any daemon's — printing the
                # in-memory list too would show each driver inversion
                # twice.
                print(
                    "[lock-witness] sidecar findings (all processes, "
                    "incl. spawned daemons):"
                )
                print(side_text)
            else:
                for v in vs:
                    print(v.render())
            session.exitstatus = 3


@pytest.fixture
def ray_start():
    """Fresh local cluster per test (reference: conftest ray_start_regular)."""
    ray_tpu.init(num_cpus=4, ignore_reinit_error=True)
    yield
    ray_tpu.shutdown()


@pytest.fixture
def ray_start_2_cpus():
    ray_tpu.init(num_cpus=2, ignore_reinit_error=True)
    yield
    ray_tpu.shutdown()
