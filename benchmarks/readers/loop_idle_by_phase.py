"""Share of the traced window in which a chip is idle while ONE phase
of the host loop is the innermost span open on the loop's thread, mean
over the chips, in percent of the window (``args``: ``phase``; ``null``
is the idle under no span of the program).

The program's spans (``obs/trace.span``) are profiler annotations on
the ``/host:CPU`` plane, on the clock of the device's operations, and
spans of one thread nest. So the loop thread's wall splits into the
innermost span open at each instant, and every idle instant of every
chip falls to exactly one phase or to none: the phases' shares and the
``null`` share sum to the cell's idle share, ``1 - busy_s / window_s``.
An enclosure's own share (``train/epoch_barrier``) is its idle outside
every span it holds.

``trace_reduce.idle_gaps`` gives a whole gap to the ONE host event that
covers most of it, on the first chip: an enclosure wins over what is
inside it, a worker thread's span that happens to run across a gap
takes it, and a gap of several short phases in a row has no name.
``span_idle_share`` reads one span's own part, on the first chip, with
whatever it encloses. This reads only the thread that holds the
``train/step`` events, every chip, and each instant once.

A program from before a phase had its span has no event of that name:
the reader returns nothing for it. The first call of a run also prints
the whole table."""

import re

from benchmarks.harness import say
from benchmarks.readers.span_idle_share import overlap
from benchmarks.trace_reduce import merged

# A span of the program is named area/phase; the runtime's own events
# (PjitFunction(...), shard_args, np.asarray) are not.
SPAN = re.compile(r"^[a-z_]+/[a-z0-9_]+$")
LOOP_MARK = "train/step"


def host_lines(trace):
    """``trace.host`` as thread lines. ``reduce()`` appends a plane's
    lines one after another and keeps of each only its name, which is
    the process's for every thread of a Python program; the profiler
    sorts a line's events by start. So a line ends where the name
    changes or the start steps back."""
    lines, name, last = [], None, 0.0
    for line_name, e in trace.host:
        if not lines or line_name != name or e.start < last:
            lines.append([])
            name = line_name
        lines[-1].append(e)
        last = e.start
    return lines


def loop_spans(trace):
    """(start, end, name) of the program's spans on the loop's thread:
    the line that holds the ``train/step`` events."""
    return [(e.start, e.end, e.name) for line in host_lines(trace)
            if any(e.name == LOOP_MARK for e in line)
            for e in line if SPAN.match(e.name) and e.end > e.start]


def innermost(spans):
    """name -> the intervals in which a span of that name is open and
    none that it encloses is."""
    out, stack, cursor = {}, [], 0.0

    def close_until(t):
        nonlocal cursor
        while stack and stack[-1][0] <= t:
            end, name = stack.pop()
            if end > cursor:
                out.setdefault(name, []).append((cursor, end))
                cursor = end

    for start, end, name in sorted(spans, key=lambda s: (s[0], -s[1])):
        close_until(start)
        if stack:
            if start > cursor:
                out.setdefault(stack[-1][1], []).append((cursor, start))
            end = min(end, stack[-1][0])    # spans of one thread nest
        stack.append((end, name))
        cursor = start
    close_until(float("inf"))
    return out


def idle_of(trace, device):
    """One chip's idle intervals inside the traced window."""
    gaps, prev = [], trace.t_first
    for a, b in merged([(o.start, o.end) for o in device.ops]):
        if a > prev:
            gaps.append((prev, a))
        prev = max(prev, b)
    if trace.t_last > prev:
        gaps.append((prev, trace.t_last))
    return gaps


def idle_by_phase(trace):
    """phase -> idle seconds under it, mean over the chips (``None``:
    under no span of the program); nothing where no thread holds a
    ``train/step`` event."""
    spans = loop_spans(trace)
    if not spans:
        return None
    own = {name: merged(iv) for name, iv in innermost(spans).items()}
    covered = merged([(a, b) for a, b, _ in spans])
    idle = [idle_of(trace, d) for d in trace.devices]
    n = len(idle)
    table = {name: sum(overlap(g, iv) for g in idle) / n
             for name, iv in own.items()}
    table[None] = sum(sum(b - a for a, b in g) - overlap(g, covered)
                      for g in idle) / n
    return table


def read(ctx, phase):
    t = ctx["trace"]
    if "loop_idle_by_phase" not in ctx:
        table = ctx["loop_idle_by_phase"] = idle_by_phase(t)
        if table:
            rows = sorted(table.items(), key=lambda kv: -kv[1])
            say(f"idle by loop phase, % of the window, mean of "
                f"{len(t.devices)} chip(s): "
                + ", ".join(f"{name or 'unnamed'} "
                            f"{100.0 * s / t.window_s:.3f}"
                            for name, s in rows)
                + f"; sum {100.0 * sum(table.values()) / t.window_s:.3f}"
                f" of an idle share "
                f"{100.0 * (1 - t.busy_s / t.window_s):.3f}")
    table = ctx["loop_idle_by_phase"]
    if not table or phase not in table:
        return None
    return 100.0 * table[phase] / t.window_s
