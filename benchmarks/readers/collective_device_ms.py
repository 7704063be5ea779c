"""Exposed collective time of the mesh step: inside one execution of
the named programs, the time during which a collective operation
(all-reduce, all-gather, reduce-scatter, all-to-all,
collective-permute, or a ``-start`` / ``-done`` half of one) is on a
chip's ``XLA Ops`` line and no other operation is: the exchange that
no compute hides. Median over the traced executions of each chip, then
the worst chip: the step ends when its slowest shard does.

A trace with no execution of the programs (a one-chip cell, a program
from before the mesh step) reads None. An execution with no collective
on its line reads 0.

The first call of a run also prints, for each collective of the step,
its median time, the bytes of its result and their share of the
interconnect's published peak (``ICI_BYTES_PER_S``): a line to read,
not a metric. A ring all-reduce moves 2 (n-1)/n of its result over
each chip's links and an all-gather (n-1)/n, so the share is of the
result's bytes, a lower bound of the traffic."""

import bisect
import re
import statistics

from benchmarks import trace_reduce
from benchmarks.harness import say
from benchmarks.readers.span_idle_share import overlap

# Google Cloud documentation, "TPU v5e", as the on-chip-measurement
# guide quotes it: 1,600 Gbit/s of chip-to-chip interconnect a chip.
ICI_BYTES_PER_S = {"TPU v5 lite": 200e9, "TPU v5e": 200e9}

_COLLECTIVE = re.compile(
    r"^%?(all-reduce|all-gather|reduce-scatter|all-to-all|"
    r"collective-permute)(-start|-done)?(?![\w-])")
_SHAPE = re.compile(r"\b(pred|[su](?:8|16|32|64)|bf16|f16|f32|f64)"
                    r"\[([\d,]*)\]")
_BYTES = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2,
          "f16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
          "f64": 8}


def collective_of(op_name):
    """'all-reduce' for '%all-reduce.8 = f32[65536,17]{...}
    all-reduce(...)', None for an operation that is no collective. A
    fusion is told by its own name, so one that merely *feeds* a
    collective is compute."""
    m = _COLLECTIVE.match(str(op_name).strip())
    return m.group(1) + (m.group(2) or "") if m else None


def result_bytes(op_name):
    """Bytes of the operation's result, from the HLO text the trace
    names it by (a tuple's parts summed); 0 if it has none."""
    head = str(op_name).split(" = ", 1)
    if len(head) < 2:
        return 0
    # the result type ends where the opcode's argument list opens
    result = re.split(r"\s[\w-]+\(", head[1], maxsplit=1)[0]
    total = 0
    for dtype, dims in _SHAPE.findall(result):
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _BYTES[dtype]
    return total


def exposed_seconds(collectives, others):
    """Length of the intervals ``collectives`` outside ``others``."""
    coll = trace_reduce.merged(collectives)
    return (sum(b - a for a, b in coll)
            - overlap(coll, trace_reduce.merged(others)))


def by_chip(trace, programs):
    """{chip: [(exposed s, {collective op: (s, bytes)}), ...]}: one
    entry for every whole execution of ``programs`` on that chip."""
    out = {}
    for d in trace.devices:
        ops = sorted(d.ops, key=lambda o: o.start)
        starts = [o.start for o in ops]
        for m in d.modules:
            if trace_reduce.program_name(m.name) not in programs:
                continue
            coll, other, each = [], [], {}
            i = bisect.bisect_left(starts, m.start)
            while i < len(ops) and ops[i].start < m.end:
                o = ops[i]
                i += 1
                span = (o.start, min(o.end, m.end))
                if collective_of(o.name) is None:
                    other.append(span)
                    continue
                coll.append(span)
                key = o.name.split(" = ", 1)[0].strip()
                s, _ = each.get(key, (0.0, 0))
                each[key] = (s + span[1] - span[0], result_bytes(o.name))
            out.setdefault(d.name, []).append(
                (exposed_seconds(coll, other), each))
    return out


def _say_each(chips, device_kind):
    peak = ICI_BYTES_PER_S.get(device_kind)
    name, runs = max(chips.items(), key=lambda kv: statistics.median(
        r[0] for r in kv[1]))
    keys = sorted({k for _, each in runs for k in each})
    for k in keys:
        s = statistics.median(each[k][0] for _, each in runs if k in each)
        nb = max(each[k][1] for _, each in runs if k in each)
        share = (f", {100 * nb / s / peak:.1f}% of {peak / 1e9:.0f} GB/s"
                 if s and peak else "")
        say(f"collective {k}: {1e3 * s:.3f} ms a step on {name}, result "
            f"{nb} B{share}")
    if not keys:
        say("collectives: none on the op line of the step's executions")


def read(ctx, programs):
    key = "collective_device_ms:" + ",".join(programs)
    if key not in ctx:
        ctx[key] = chips = by_chip(ctx["trace"], programs)
        if chips:
            _say_each(chips, ctx.get("device_kind"))
    chips = ctx[key]
    if not chips:
        return None
    return 1e3 * max(statistics.median(r[0] for r in runs)
                     for runs in chips.values())
