"""The limit that tests/conftest.py arms around every test's setup, call
and teardown: a hang is one failure that says where every thread stood."""
import signal
import threading
import time

import pytest


def test_a_hang_fails_with_the_stack_of_every_thread(time_limit):
    release = threading.Event()

    def parked_beside_the_hang():
        release.wait(30)

    beside = threading.Thread(target=parked_beside_the_hang, daemon=True)
    beside.start()
    t0 = time.monotonic()
    try:
        with pytest.raises(pytest.fail.Exception) as failure:
            with time_limit(1, "call of a test that waits for ever"):
                threading.Event().wait()
    finally:
        release.set()
        beside.join(30)
    assert 1 <= time.monotonic() - t0 < 20
    said = str(failure.value)
    assert "call of a test that waits for ever took more than 1 s" in said
    # The main thread, where it hung, and the thread beside it.
    assert "test_a_hang_fails_with_the_stack_of_every_thread" in said
    assert "parked_beside_the_hang" in said


def test_a_limit_ends_with_what_it_bounds(time_limit):
    # This call runs under the hook's own limit; a nested one hands the
    # handler back as it found it, and the timer with what is left of
    # it: the time spent inside comes off the outer limit too.
    handler = signal.getsignal(signal.SIGALRM)
    left = signal.getitimer(signal.ITIMER_REAL)[0]
    assert left > 1
    with time_limit(1, "call of a test that ends in time"):
        assert 0 < signal.getitimer(signal.ITIMER_REAL)[0] <= 1
        time.sleep(0.3)
    assert 1 < signal.getitimer(signal.ITIMER_REAL)[0] <= left - 0.3
    assert signal.getsignal(signal.SIGALRM) is handler
    time.sleep(1.2)  # the second it was given passes, and nothing is raised
