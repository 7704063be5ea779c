"""The one reading rule (README "How a rate is read").

A *reading* is the work done between two consecutive sync points over
the monotonic time between them. A reading cut by either edge of the
measured window is dropped, and the cell's end-to-end rate is ALL the
work over ALL the time of the whole cycles of readings inside it
(rate_from_readings): every stall, flush and epoch barrier of the span
is in it, and where the window's edge falls among the steps costs
nothing, because the span begins and ends on sync points and holds
whole cycles (PERF.md, Findings, PR 24: what PR 22's
steps-over-window rate lacked). The median reading, which one stall
or one barrier hardly moves, stands beside it as a per-layer metric.
"""

from __future__ import annotations

import statistics
from typing import Dict, List, Sequence, Tuple

Reading = Tuple[float, float, float]  # (t_begin, t_end, work units)


def readings_between_syncs(syncs: Sequence[Tuple[float, float]]
                           ) -> List[Reading]:
    """``syncs`` are (monotonic time, cumulative work) stamps taken at
    consecutive sync points; each adjacent pair is one reading."""
    out = []
    for (t0, w0), (t1, w1) in zip(syncs, syncs[1:]):
        if t1 <= t0 or w1 <= w0:
            raise ValueError(
                f"sync points must advance: ({t0}, {w0}) -> ({t1}, {w1})")
        out.append((t0, t1, w1 - w0))
    return out


def rate_from_readings(readings: Sequence[Reading], start: float,
                       end: float, cycle: int = 1, min_readings: int = 3
                       ) -> Dict[str, float]:
    """The rates of a window, from the readings wholly inside
    [start, end] (a reading cut by either edge is dropped), taken over
    the longest run of whole cycles of readings from the window's
    first sync point. A cycle is the readings after which the traffic
    repeats (one epoch of a train corpus), so every span holds the
    same work wherever the window's edges fall.

    ``rate`` is ALL the work over ALL the time of the span, stalls and
    epoch barriers included: the cell's end-to-end rate.

    ``median`` is the median reading, the steady pace between stalls:
    the per-layer ``steady_rate.*`` that stands beside it, so that a
    change in ``rate`` can be told apart into the steady step and what
    interrupts it."""
    inside = sorted((a, b, w) for a, b, w in readings
                    if a >= start and b <= end)
    n = (len(inside) // cycle) * cycle
    if n < max(min_readings, cycle):
        raise ValueError(
            f"{len(inside)} readings lie wholly inside the window "
            f"({len(readings)} taken); a cycle is {cycle} and "
            f"{min_readings} are the least a rate is taken from: "
            "lengthen --seconds or shorten the reading or the corpus "
            "in the traffic file")
    span = inside[:n]
    for (_, b, _), (a, _, _) in zip(span, span[1:]):
        if a < b:
            raise ValueError("readings of one span must not overlap")
    rates = [w / (b - a) for a, b, w in span]
    work = sum(w for _, _, w in span)
    return {
        "rate": work / (span[-1][1] - span[0][0]),
        "median": statistics.median(rates),
        "n": n,
        "dropped": len(readings) - n,
        "min": min(rates),
        "max": max(rates),
        "work": work,
        "span": (span[0][0], span[-1][1]),
        "rates": rates,
    }
