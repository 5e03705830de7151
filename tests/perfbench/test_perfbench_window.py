"""The window loop on a stub: the rate is rounds over the time to the last
completion, failed rounds are counted, and the window closes on the clock."""
import pytest

from perfbench.jobs.train import auc, run_window


class Clock:
    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t


def stub(clock, cost, fail_at=()):
    calls = []

    def update():
        calls.append(clock.t)
        clock.t += cost
        if len(calls) in fail_at:
            return "empty tree"
        return None
    return update, calls


@pytest.mark.parametrize("cost,seconds,want", [
    (17.0, 50.0, 3),        # 17, 34, 51: the third call returns past 50
    (25.0, 50.0, 2),        # the second returns at exactly 50
    (30.0, 1.0, 2),         # at least two rounds however short the window
    (4.0, 10.0, 3),
])
def test_window_closes_when_a_call_returns_past_the_clock(cost, seconds, want):
    clock = Clock()
    update, calls = stub(clock, cost)
    w = run_window(update, seconds, 2, clock=clock)
    assert w["attempted"] == w["completed"] == want == len(calls)
    assert w["failed"] == 0
    assert w["window_s"] == pytest.approx(want * cost)
    assert w["rounds_per_s"] == pytest.approx(1.0 / cost)


def test_rate_counts_the_wait_for_the_last_round():
    clock = Clock()
    update, _ = stub(clock, 10.0)

    def finish():           # the device finishes 2 s after the last return
        clock.t += 2.0
    w = run_window(update, 15.0, 2, clock=clock, finish=finish)
    assert w["completed"] == 2
    assert w["rounds_per_s"] == pytest.approx(2 / 22.0)


def test_failed_rounds_are_attempted_not_completed():
    clock = Clock()
    update, _ = stub(clock, 10.0, fail_at=(2,))
    w = run_window(update, 25.0, 2, clock=clock)
    assert (w["attempted"], w["failed"], w["completed"]) == (3, 1, 2)
    assert w["rounds_per_s"] == pytest.approx(2 / 30.0)


def test_a_round_that_raises_is_a_failed_round_and_the_loop_ends():
    clock = Clock()

    def update():
        clock.t += 1.0
        raise RuntimeError("boom")
    w = run_window(update, 100.0, 2, clock=clock)
    assert w["failed"] == w["attempted"] == 3 and w["completed"] == 0
    assert w["rounds_per_s"] == 0.0


def test_auc_by_ranks_with_ties():
    assert auc([0, 0, 1, 1], [0.1, 0.2, 0.3, 0.4]) == 1.0
    assert auc([1, 1, 0, 0], [0.1, 0.2, 0.3, 0.4]) == 0.0
    assert auc([0, 1, 0, 1], [0.5, 0.5, 0.5, 0.5]) == 0.5
    assert auc([0, 1, 1], [0.2, 0.2, 0.9]) == pytest.approx(0.75)
