"""Step timing: the windowed examples/sec of the loss lines. (Stages
are timed and put on the profiler's clock by ``obs/trace.span``.)"""

from __future__ import annotations

import time


class StepTimer:
    """Examples/sec over the WINDOW since the rate was last read; call
    ``tick(n_examples)`` after each step result is materialised.

    ``consume_window_rate()`` reports and resets the window, so
    consecutive log lines show the rate between logs rather than a
    cumulative average anchored at construction — a cumulative figure
    would absorb first-step jit compilation and every validation/
    checkpoint pause into all later lines, understating the loop rate
    worst on short runs. ``total_examples_per_sec`` keeps the
    whole-run figure (including those pauses) for end-of-run
    summaries."""

    def __init__(self):
        self.reset()

    def reset(self) -> None:
        self._t0 = time.perf_counter()
        self._win_t0 = self._t0
        self._examples = 0
        self._win_examples = 0
        self._steps = 0

    def tick(self, n_examples: int) -> None:
        self._examples += n_examples
        self._win_examples += n_examples
        self._steps += 1

    def consume_window_rate(self) -> float:
        """Examples/sec since the previous call, CONSUMING the window —
        an explicit method (not a property) because reading it twice
        per step would silently deflate the second reading."""
        now = time.perf_counter()
        dt = now - self._win_t0
        rate = self._win_examples / dt if dt > 0 else 0.0
        self._win_t0 = now
        self._win_examples = 0
        return rate

    @property
    def total_examples_per_sec(self) -> float:
        dt = time.perf_counter() - self._t0
        return self._examples / dt if dt > 0 else 0.0

    @property
    def steps(self) -> int:
        return self._steps
