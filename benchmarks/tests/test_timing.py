"""Rate and percentile arithmetic of the window."""
import pytest

from benchmarks.lib import timing


def test_rate_is_all_the_work_over_all_the_time():
    # forty steps of 0.25 s, one of them with a 2 s stall: 12 s of window
    steps = [0.25] * 40
    steps[17] = 2.25
    assert timing.rate(len(steps), 100, sum(steps)) == pytest.approx(
        4000 / 12.0)
    # without the stall it would have read 400
    assert timing.rate(40, 100, 10.0) == pytest.approx(400.0)
    with pytest.raises(ValueError):
        timing.rate(0, 100, 10.0)


def test_percentile_interpolates():
    values = list(range(1, 101))
    assert timing.percentile(values, 95) == pytest.approx(95.05)
    assert timing.percentile([3.0], 95) == 3.0


def test_step_tail_is_the_tail_of_single_steps():
    steps = [0.068] * 400
    for i in range(0, 400, 10):    # every tenth step stalls
        steps[i] = 0.068 + 0.4
    assert timing.step_ms_p95(steps) == pytest.approx(468.0)
    steps = [0.068] * 400
    steps[100] = 0.068 + 0.4       # one stall in 400 is past the percentile
    assert timing.step_ms_p95(steps) == pytest.approx(68.0)


def test_the_window_is_the_wall_time_of_its_steps():
    from benchmarks.lib import harness

    class Driver:
        def step(self, i):
            return 1.0

    times, losses, window_s = harness.measure(Driver(), 0.05, 0)
    assert len(times) == len(losses) >= 1
    assert sum(times) == pytest.approx(window_s)
    assert window_s >= 0.05
