"""Share of the traced window in which the first chip is idle while the
program's span of this name is open on the host (``obs/trace.span``
opens a profiler annotation of the span's name while a trace is live):
the overlap of the chip's idle gaps with the span's events, in percent
of the window.

``trace_reduce.idle_gaps`` gives each gap one name, that of the host
event covering most of it, or none if nothing covers half: right for a
gap with one cause, but the gap after a sync point is several short
phases in a row (input wait, transfer, dispatch), and a span on a
worker thread that happens to run across it takes it whole. This reads
each phase's own part. ``host_idle_share`` counts the gaps *no* host
event covers; the program's phases now cover them."""

from benchmarks.trace_reduce import merged


def overlap(a, b):
    """Total length of the intersection of two merged interval lists."""
    total, i, j = 0.0, 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def idle_gaps(trace):
    """The first chip's idle intervals inside the traced window."""
    gaps, prev = [], trace.t_first
    for a, b in merged([(o.start, o.end) for o in trace.devices[0].ops]):
        if a > prev:
            gaps.append((prev, a))
        prev = max(prev, b)
    if trace.t_last > prev:
        gaps.append((prev, trace.t_last))
    return gaps


def read(ctx, span):
    t = ctx["trace"]
    open_ = merged([(e.start, e.end) for _, e in t.host if e.name == span])
    return 100.0 * overlap(idle_gaps(t), open_) / t.window_s
