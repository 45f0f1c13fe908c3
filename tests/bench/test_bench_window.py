"""The measured window runs solves two deep: solve k+1 is dispatched before
solve k is waited on, never more than one waits in the queue, and every
solve dispatched is waited on and counted.  Driven with a fake device
whose clock moves only while the host waits on a solve."""
import pytest

from bench.harness import time_window


class FakeDevice:
    """Solves of `solve_s` seconds each (binary fractions, so the clock
    adds them exactly), run in order; the clock stands still except
    while the host waits for a solve to end."""

    def __init__(self, solve_s):
        self.solve_s = solve_s
        self.now = 0.0
        self.calls = []
        self.dispatched = 0
        self.waited = []
        self.most_queued = 0

    def clock(self):
        return self.now

    def dispatch(self):
        self.calls.append("dispatch")
        self.dispatched += 1
        # the solves dispatched and not yet waited on, less the running one
        self.most_queued = max(self.most_queued,
                               self.dispatched - len(self.waited) - 1)
        return self.dispatched

    def wait(self, solve):
        self.calls.append("wait")
        assert solve == len(self.waited) + 1, "solves waited on out of order"
        self.now += self.solve_s
        self.waited.append(solve)
        return solve


def _window(solve_s, seconds):
    dev = FakeDevice(solve_s)
    out = time_window(dev.dispatch, dev.wait, seconds, clock=dev.clock)
    return dev, out


@pytest.mark.parametrize("solve_s, seconds", [
    (0.0625, 1.0), (0.25, 1.0), (0.75, 10.0), (1.0, 3.0)])
def test_two_deep_order_and_count(solve_s, seconds):
    dev, (last, solves, t_start, t_end) = _window(solve_s, seconds)
    assert dev.calls[:3] == ["dispatch", "dispatch", "wait"]
    assert dev.calls[3:-1] == ["dispatch", "wait"] * (solves - 2)
    assert dev.calls[-1] == "wait"
    assert dev.most_queued == 1
    assert dev.waited == list(range(1, dev.dispatched + 1))
    assert solves == dev.dispatched == last
    assert t_start == 0.0 and t_end == dev.now == solves * solve_s
    # the second-to-last solve was the first to end after `seconds`
    assert (solves - 1) * solve_s >= seconds > (solves - 2) * solve_s


def test_window_shorter_than_one_solve_counts_two():
    dev, (last, solves, t_start, t_end) = _window(0.5, 0.01)
    assert dev.calls == ["dispatch", "dispatch", "wait", "wait"]
    assert solves == 2 and last == 2 and t_end == 1.0
