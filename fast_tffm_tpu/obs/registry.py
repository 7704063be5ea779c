"""Metrics registry: counters, gauges, fixed-bucket histograms.

Dependency-free by design (stdlib only): the registry is imported by
hot-path modules (data/pipeline.py, data/cparser.py) whose import cost
and thread model must stay trivial. Thread-safety contract: the
single-call forms (``count``/``set``/``observe``) and
``snapshot``/``merge`` all mutate/read under one registry lock — the
pipeline mutates from the prefetch worker thread while the train loop
snapshots from the main thread, so instrumented sites MUST use those
forms. The accessor forms (``counter()``/``gauge()``/``histogram()``)
hand back the raw metric object, whose methods are NOT locked — they
exist for single-threaded setup/tests and read-side tooling. Per-point
cost is a lock + dict lookup + float add, cheap enough for per-batch
(not per-line) cadence.

Histograms use FIXED bucket boundaries so two histograms from different
workers (or different flush windows) merge by adding bucket counts —
the property the sharded path's per-worker event streams rely on.
Quantiles are bucket-upper-bound estimates: exact enough to tell a 2 ms
step from a 200 ms stall, which is the job.
"""

from __future__ import annotations

import bisect
import threading
from typing import Dict, List, Optional, Sequence, Tuple


def default_time_buckets() -> Tuple[float, ...]:
    """Exponential seconds ladder, 100 us .. ~100 s: covers a 20 us TPU
    step rounded up through a multi-second device-link stall."""
    out, b = [], 1e-4
    while b < 200.0:
        out.append(b)
        b *= 2.0
    return tuple(out)


class Counter:
    """Monotonic accumulator (ints or float seconds/bytes)."""

    __slots__ = ("value",)

    def __init__(self) -> None:
        self.value = 0.0

    def inc(self, n: float = 1.0) -> None:
        self.value += n


class Gauge:
    """Last-written value (rates, depths, AUC)."""

    __slots__ = ("value",)

    def __init__(self) -> None:
        self.value: Optional[float] = None

    def set(self, v: float) -> None:
        self.value = float(v)


class Histogram:
    """Fixed-bucket histogram with sum/min/max and estimated quantiles.

    ``bounds`` are bucket UPPER bounds (ascending); an implicit overflow
    bucket catches everything past the last bound. ``merge`` requires
    identical bounds — guaranteed within a run because the registry
    owns bucket choice per metric name, and across workers because all
    workers run the same code.
    """

    __slots__ = ("bounds", "counts", "count", "sum", "min", "max")

    def __init__(self, bounds: Optional[Sequence[float]] = None) -> None:
        self.bounds: Tuple[float, ...] = tuple(
            bounds if bounds is not None else default_time_buckets())
        if list(self.bounds) != sorted(set(self.bounds)):
            raise ValueError(
                f"histogram bounds must be strictly increasing, "
                f"got {self.bounds}")
        self.counts: List[int] = [0] * (len(self.bounds) + 1)
        self.count = 0
        self.sum = 0.0
        self.min: Optional[float] = None
        self.max: Optional[float] = None

    def observe(self, v: float) -> None:
        v = float(v)
        self.counts[bisect.bisect_left(self.bounds, v)] += 1
        self.count += 1
        self.sum += v
        if self.min is None or v < self.min:
            self.min = v
        if self.max is None or v > self.max:
            self.max = v

    def quantile(self, q: float) -> Optional[float]:
        """Estimated q-quantile: the upper bound of the bucket holding
        the q-th point (min/max for the open ends). None when empty."""
        if not self.count:
            return None
        target = q * self.count
        seen = 0
        for i, c in enumerate(self.counts):
            seen += c
            if seen >= target and c:
                if i >= len(self.bounds):
                    return self.max
                return min(self.bounds[i],
                           self.max if self.max is not None
                           else self.bounds[i])
        return self.max

    def merge(self, other: "Histogram") -> None:
        if other.bounds != self.bounds:
            raise ValueError(
                "cannot merge histograms with different bounds: "
                f"{self.bounds} vs {other.bounds}")
        for i, c in enumerate(other.counts):
            self.counts[i] += c
        self.count += other.count
        self.sum += other.sum
        for attr, pick in (("min", min), ("max", max)):
            ov = getattr(other, attr)
            if ov is not None:
                sv = getattr(self, attr)
                setattr(self, attr, ov if sv is None else pick(sv, ov))

    def summary(self) -> Dict[str, object]:
        """JSON-ready fixed-quantile summary + the raw mergeable state
        (bounds/counts ride along so a reader can re-merge windows)."""
        mean = self.sum / self.count if self.count else None
        return {
            "count": self.count, "sum": self.sum, "mean": mean,
            "min": self.min, "max": self.max,
            "p50": self.quantile(0.50), "p90": self.quantile(0.90),
            "p99": self.quantile(0.99),
            "bounds": list(self.bounds), "counts": list(self.counts),
        }

    @classmethod
    def from_summary(cls, s: Dict[str, object]) -> "Histogram":
        """Inverse of ``summary()`` — fmstat re-merges flush windows and
        workers through the same merge() the live registry uses."""
        h = cls(bounds=s["bounds"])
        h.counts = list(s["counts"])
        h.count = int(s["count"])
        h.sum = float(s["sum"])
        h.min = s["min"]
        h.max = s["max"]
        return h


class MetricsRegistry:
    """Named metric store: get-or-create accessors, a consistent
    snapshot, and worker-merge. One lock serializes mutation against
    snapshot (prefetch thread vs driver thread)."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._counters: Dict[str, Counter] = {}
        self._gauges: Dict[str, Gauge] = {}
        self._hists: Dict[str, Histogram] = {}

    def counter(self, name: str) -> Counter:
        with self._lock:
            c = self._counters.get(name)
            if c is None:
                c = self._counters[name] = Counter()
            return c

    def gauge(self, name: str) -> Gauge:
        with self._lock:
            g = self._gauges.get(name)
            if g is None:
                g = self._gauges[name] = Gauge()
            return g

    def histogram(self, name: str,
                  bounds: Optional[Sequence[float]] = None) -> Histogram:
        with self._lock:
            h = self._hists.get(name)
            if h is None:
                h = self._hists[name] = Histogram(bounds)
            return h

    # Single-call forms for instrumented sites: get-or-create AND
    # mutate under the lock, so a worker-thread point can never tear
    # against a concurrent snapshot() (see module docstring).
    def count(self, name: str, n: float = 1.0) -> None:
        with self._lock:
            c = self._counters.get(name)
            if c is None:
                c = self._counters[name] = Counter()
            c.inc(n)

    def set(self, name: str, v: float) -> None:
        with self._lock:
            g = self._gauges.get(name)
            if g is None:
                g = self._gauges[name] = Gauge()
            g.set(v)

    def observe(self, name: str, v: float,
                bounds: Optional[Sequence[float]] = None) -> None:
        with self._lock:
            h = self._hists.get(name)
            if h is None:
                h = self._hists[name] = Histogram(bounds)
            h.observe(v)

    def snapshot(self) -> Dict[str, Dict[str, object]]:
        """One JSON-ready dict: {"counters": {...}, "gauges": {...},
        "hists": {name: summary}}. Cumulative (not delta) — readers
        diff consecutive snapshots for windowed rates, so a dropped
        flush loses resolution, never mass."""
        with self._lock:
            return {
                "counters": {k: c.value
                             for k, c in self._counters.items()},
                "gauges": {k: g.value for k, g in self._gauges.items()
                           if g.value is not None},
                "hists": {k: h.summary()
                          for k, h in self._hists.items()},
            }

    def merge(self, other: "MetricsRegistry") -> None:
        """Fold another worker's registry in: counters add, histograms
        bucket-merge, gauges last-writer-wins (per-worker gauges should
        be namespaced by process index before merging)."""
        snap = other.snapshot()
        with self._lock:
            for k, v in snap["counters"].items():
                c = self._counters.get(k)
                if c is None:
                    c = self._counters[k] = Counter()
                c.inc(v)
            for k, v in snap["gauges"].items():
                g = self._gauges.get(k)
                if g is None:
                    g = self._gauges[k] = Gauge()
                g.set(v)
            for k, s in snap["hists"].items():
                h = self._hists.get(k)
                if h is None:
                    self._hists[k] = Histogram.from_summary(s)
                else:
                    h.merge(Histogram.from_summary(s))
