"""Share of the traced window in which a chip is idle while the loop
waits for its feed, by the STAGE of the feed it is waiting for, mean
over the chips, in percent of the window (``args``: ``stage``, one of
``place``, ``emit``, ``build``, ``scan``, ``none``).

``loop_idle_by_phase`` reads the loop's thread alone and calls all of
that idle ``train/input_wait`` (an epoch's first wait
``pipeline/first_batch``). The feed behind that wait is threads in
series (``fm-scan`` -> the coordinator over the ring of ``fm-build-<i>``
workers -> ``fm-place`` -> the loop), each with a span around its WORK
and none around its waiting (the hand-overs' waits are counters, never
annotations: ``data/pipeline.py`` ``_read_ahead``). So at an idle
instant under one of the two phases, the stage the loop is waiting for
is the stage NEAREST THE LOOP whose work span is open on any thread:

1. ``feed/place`` open: ``place``;
2. else ``pipeline/emit`` open: ``emit``;
3. else ``pipeline/ring_wait`` open (the coordinator at the ring's
   head) or any ``pipeline/build_worker`` open: ``build``;
4. else ``pipeline/scan`` open: ``scan``;
5. else ``none``: every thread of the feed is between spans (the GIL,
   the scheduler, a queue's hand-over).

Every such instant falls to exactly one of the five, so they sum to
``loop_idle_by_phase``'s shares of the two phases, whose functions this
imports. A trace from a program without a ``pipeline/scan`` span (the
scanner had none before PR 54) returns nothing. The first call of a run
also prints the table."""

from benchmarks.harness import say
from benchmarks.readers import loop_idle_by_phase as by_phase
from benchmarks.readers.span_idle_share import overlap
from benchmarks.trace_reduce import merged

WAITS = ("train/input_wait", "pipeline/first_batch")
# nearest the loop first
STAGES = (("place", ("feed/place",)),
          ("emit", ("pipeline/emit",)),
          ("build", ("pipeline/ring_wait", "pipeline/build_worker")),
          ("scan", ("pipeline/scan",)))
NONE = "none"


def intersect(a, b):
    """The intersection of two merged interval lists, as one."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            out.append((lo, hi))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def idle_by_stage(trace):
    """stage -> idle seconds while the loop waits for it, mean over the
    chips; nothing where the trace has no loop thread or no scanner's
    span."""
    spans = by_phase.loop_spans(trace)
    opened = {}
    for _, e in trace.host:
        if e.end > e.start:
            opened.setdefault(e.name, []).append((e.start, e.end))
    if not spans or "pipeline/scan" not in opened:
        return None
    own = by_phase.innermost(spans)
    waiting = merged([iv for name in WAITS for iv in own.get(name, [])])
    gaps = [intersect(by_phase.idle_of(trace, d), waiting)
            for d in trace.devices]
    n = len(gaps)
    table, nearer, claimed = {}, [], 0.0
    for stage, names in STAGES:
        # open here or at a stage nearer the loop, less what the nearer
        # ones took: each instant goes to the nearest stage once
        nearer = merged(nearer + [iv for name in names
                                  for iv in opened.get(name, [])])
        upto = sum(overlap(g, nearer) for g in gaps) / n
        table[stage], claimed = upto - claimed, upto
    table[NONE] = sum(b - a for g in gaps for a, b in g) / n - claimed
    return table


def read(ctx, stage):
    t = ctx["trace"]
    if "feed_idle_by_stage" not in ctx:
        table = ctx["feed_idle_by_stage"] = idle_by_stage(t)
        if table:
            say(f"idle while the loop waits for its feed, by the stage "
                f"waited for, % of the window, mean of {len(t.devices)} "
                f"chip(s): "
                + ", ".join(f"{name} {100.0 * s / t.window_s:.3f}"
                            for name, s in table.items())
                + f"; sum {100.0 * sum(table.values()) / t.window_s:.3f}"
                f" (loop_idle_by_phase: {' + '.join(WAITS)})")
    table = ctx["feed_idle_by_stage"]
    if not table or stage not in table:
        return None
    return 100.0 * table[stage] / t.window_s
