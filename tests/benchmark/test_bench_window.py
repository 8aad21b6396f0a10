"""The window logic, pinned on a fake clock: elapsed time ends at the last
counted step's readback, an unfinished step is never counted, and a stall
inside the window lowers the rate."""

import pytest

from benchmarks.lib import window


class FakeDevice:
    """Steps take ``step_s`` each on a device that runs them one after
    another; ``wait`` advances the clock to the step's completion."""

    def __init__(self, step_s=0.1, stall_at=None, stall_s=0.0):
        self.now = 0.0
        self.free_at = 0.0
        self.step_s, self.stall_at, self.stall_s = step_s, stall_at, stall_s
        self.dispatched, self.finished = [], []

    def clock(self):
        return self.now

    def dispatch(self, k):
        if k == self.stall_at:
            self.now += self.stall_s            # the host stalls
        start = max(self.now, self.free_at)
        self.free_at = start + self.step_s
        self.dispatched.append(k)
        return (k, self.free_at)

    def wait(self, handle):
        k, done = handle
        self.now = max(self.now, done)
        self.finished.append(k)


def run(dev, seconds, in_flight=2):
    return window.run_steps(dev.dispatch, dev.wait, seconds, in_flight,
                            clock=dev.clock)


def test_elapsed_ends_at_last_counted_readback():
    dev = FakeDevice(step_s=0.1)
    win = run(dev, seconds=1.05)
    assert win.counted == 11                    # first boundary past 1.05 s
    assert win.elapsed_s == pytest.approx(1.1)
    assert win.elapsed_s == win.ends_s[-1] >= 1.05
    assert win.ends_s[-2] < 1.05                # stopped at the FIRST one


def test_rate_is_work_over_time_to_the_boundary_not_over_seconds():
    for seconds in (1.0, 1.01, 1.05, 1.09):
        win = run(FakeDevice(step_s=0.1), seconds)
        assert win.counted / win.elapsed_s == pytest.approx(10.0)


def test_unfinished_step_is_not_counted():
    dev = FakeDevice(step_s=0.1)
    win = run(dev, seconds=0.5, in_flight=3)
    assert win.drained == 2                     # in flight when it closed
    assert len(dev.dispatched) == win.counted + win.drained
    assert dev.finished == dev.dispatched       # all waited for before return
    assert win.counted == 5 and win.elapsed_s == pytest.approx(0.5)


def test_steps_in_flight_are_fixed():
    dev = FakeDevice(step_s=0.1)
    seen = []
    orig = dev.wait

    def wait(handle):
        seen.append(len(dev.dispatched) - len(dev.finished))
        orig(handle)

    window.run_steps(dev.dispatch, wait, 1.0, 2, clock=dev.clock)
    assert set(seen[:-1]) == {2}


def test_stall_lowers_the_rate():
    steady = run(FakeDevice(step_s=0.1), 2.0)
    stalled = run(FakeDevice(step_s=0.1, stall_at=8, stall_s=0.5), 2.0)
    assert steady.counted / steady.elapsed_s == pytest.approx(10.0)
    assert stalled.counted / stalled.elapsed_s < 9.0
    slow = window.slowest(stalled.step_s, 1)[0]
    assert slow[1] == pytest.approx(0.5, abs=0.11)  # the file shows the step


def test_short_host_stall_hides_behind_a_step_in_flight():
    dev = FakeDevice(step_s=0.1, stall_at=8, stall_s=0.05)
    win = run(dev, 2.0)
    assert win.counted / win.elapsed_s == pytest.approx(10.0)


def test_percentile_and_slowest():
    assert window.percentile([1, 2, 3, 4, 5], 50) == 3
    assert window.percentile(list(range(101)), 95) == 95
    assert window.slowest([0.1, 0.5, 0.2], 2) == [(1, 0.5), (2, 0.2)]
    with pytest.raises(ValueError):
        window.percentile([], 50)


def test_a_traced_window_is_shortened_aloud(capsys):
    traffic = {"trace_seconds": 4}
    assert window.length(30.0, traffic, trace=0) == 30.0
    assert capsys.readouterr().err == ""
    assert window.length(30.0, traffic, trace=1) == 4.0
    assert "measures 4 s of the 30 s asked for" in capsys.readouterr().err
    # a request shorter than the trace's own length stands
    assert window.length(2.0, traffic, trace=1) == 2.0
    assert capsys.readouterr().err == ""
