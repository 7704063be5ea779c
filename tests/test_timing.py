"""utils/timing.py: StepTimer window semantics (ISSUE 2 satellites)."""

import pytest

import fast_tffm_tpu.utils.timing as timing
from fast_tffm_tpu.utils.timing import StepTimer


class FakeClock:
    def __init__(self, t=100.0):
        self.t = t

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt


@pytest.fixture
def clock(monkeypatch):
    c = FakeClock()
    monkeypatch.setattr(timing.time, "perf_counter", c)
    return c


def test_consume_resets_window(clock):
    t = StepTimer()
    clock.advance(2.0)
    t.tick(100)
    assert t.consume_window_rate() == pytest.approx(50.0)
    # window consumed: the next read covers only what came after
    clock.advance(1.0)
    t.tick(10)
    assert t.consume_window_rate() == pytest.approx(10.0)
    # and an immediate re-read sees an empty window, not a repeat
    clock.advance(1.0)
    assert t.consume_window_rate() == 0.0


def test_zero_dt_guard(clock):
    t = StepTimer()
    t.tick(100)  # no clock advance: dt == 0 exactly
    assert t.consume_window_rate() == 0.0
    assert t.total_examples_per_sec == 0.0


def test_total_rate_includes_pauses(clock):
    t = StepTimer()
    clock.advance(1.0)
    t.tick(100)
    t.consume_window_rate()
    clock.advance(9.0)  # a long validation/checkpoint pause
    t.tick(100)
    # window rate excludes everything before its reset...
    assert t.consume_window_rate() == pytest.approx(100 / 9.0)
    # ...total anchors at construction, absorbing the pause
    assert t.total_examples_per_sec == pytest.approx(200 / 10.0)
    assert t.steps == 2


def test_reset_clears_everything(clock):
    t = StepTimer()
    clock.advance(1.0)
    t.tick(50)
    t.reset()
    clock.advance(2.0)
    t.tick(10)
    assert t.steps == 1
    assert t.total_examples_per_sec == pytest.approx(5.0)
