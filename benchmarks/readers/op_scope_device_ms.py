"""Device-busy time of the operations whose jax op path has a NAMED
component, whatever scope of ``scope_device_ms.SCOPES`` holds it:
``anova_scan``, the ANOVA dynamic programme of FM of order > 2
(ops/interaction.py ``_anova_terms``), sits inside ``interaction``, so
``interaction_ms`` holds it and this reads it alone. Inside one
execution of the named programs, median over the traced executions, on
``scope_device_ms``'s interval arithmetic (the union of the operations'
intervals, cut to the execution's).

The component is the argument: bare (``jvp(interaction)/anova_scan/
while/body/add``) or inside transformations (``transpose(jvp(
anova_scan))``); a primitive or a jitted function of the same name is
no match. A program none of whose operations carries it (an order-2
step, a program from before the scope) reads None.

``share: "roofline"`` gives, in percent, the least time for the work
the scan must do whatever implements it over the time measured, by
execution (``scan_least_seconds``, below: the width is the one the
execution's ``train/step`` span says, paired as drivers/train_bags.py
pairs them), median over executions. None where the spans say no
width."""

import bisect
import functools
import re
import statistics

from benchmarks import peaks, trace_reduce, xplane_meta
from benchmarks.harness import say
from benchmarks.readers import scope_device_ms


@functools.lru_cache(maxsize=None)
def _wrapped(component):
    """``component`` as a path component, bare or inside
    transformations; ``jit(name)`` is a function's name."""
    return re.compile(r"^(?:(?!jit\()[\w.]+\()*" + re.escape(component)
                      + r"\)*$")


def has_component(op_path, component):
    """Whether ``component`` is one of the path's scopes (the last
    component is the primitive, and no scope)."""
    parts = str(op_path or "").rsplit(":", 1)[0].split("/")[:-1]
    return any(_wrapped(component).match(p) for p in parts)


def scan_least_seconds(batch, width, factor_num, order, device_kind):
    """Least time of the ANOVA kernels of degree 2..order over
    ``[batch, width, factor_num]`` float32 cells, forward and backward,
    whatever computes them: ``z`` read forward, read backward, its
    gradient written (3 x B x L x k x 4 bytes) against 6 x B x L x
    order x k operations (a multiply and an add a degree forward, twice
    that backward); the larger of the two times at the device's peaks.
    At [8192, 96, 8] on a v5e: 75.5 MB = 92 us, 0.6 us of arithmetic:
    bytes-bound."""
    p = peaks.peaks_for(device_kind)
    cells = batch * width * factor_num
    return max(3 * cells * 4 / p["bytes_per_s"],
               6 * cells * order / p["flops_per_s"])


def per_execution_ms(trace, meta, programs, component):
    """[ms] of every whole execution of ``programs`` that has
    operations in the trace: the union of the intervals of those that
    carry ``component``."""
    out = []
    for d in trace.devices:
        stats_of = meta.get(d.name, {})
        carries = {}
        ops = sorted(d.ops, key=lambda o: o.start)
        starts = [o.start for o in ops]
        for m in d.modules:
            if trace_reduce.program_name(m.name) not in programs:
                continue
            spans = []
            first = i = bisect.bisect_left(starts, m.start)
            while i < len(ops) and ops[i].start < m.end:
                o = ops[i]
                i += 1
                if o.name not in carries:
                    carries[o.name] = has_component(
                        stats_of.get(o.name, {}).get("tf_op"), component)
                if carries[o.name]:
                    spans.append((o.start, min(o.end, m.end)))
            if i > first:
                out.append(1e3 * trace_reduce.union_length(spans))
    return out


def _readings(ctx, programs, component):
    """(ms of every execution, {width: ms of its executions}), worked
    out once a run and said on a line by width."""
    key = f"op_scope_device_ms:{component}:" + ",".join(programs)
    if key in ctx:
        return ctx[key]
    path = scope_device_ms._xplane_path(ctx)
    meta = xplane_meta.read(path) if path else {}
    trace = ctx["trace"]
    runs = per_execution_ms(trace, meta, programs, component)
    by_width = {}
    if any(runs):
        from benchmarks.drivers import train_bags
        for width, devices in sorted(train_bags.executions_by_width(
                trace, programs).items()):
            sub = trace_reduce.Trace(devices, [], trace.t_first, trace.t_last)
            by_width[width] = per_execution_ms(sub, meta, programs, component)
            say(f"{component} at width {width}: {len(by_width[width])} "
                f"executions, {statistics.median(by_width[width]):.3f} ms "
                f"(min {min(by_width[width]):.3f}, max "
                f"{max(by_width[width]):.3f})")
    ctx[key] = runs, by_width
    return ctx[key]


def read(ctx, programs, component, share=None):
    runs, by_width = _readings(ctx, programs, component)
    if not any(runs):
        return None
    if share != "roofline":
        return statistics.median(runs)
    program = ctx["run"].cell.config["program"]
    general = program["General"]
    shares = []
    for width, ms in by_width.items():
        least = scan_least_seconds(
            int(program["Train"]["batch_size"]) // ctx["chips"], width,
            int(general["factor_num"]), int(general.get("order", 2)),
            ctx["device_kind"])
        shares += [100.0 * least / (t / 1e3) for t in ms if t > 0]
    return statistics.median(shares) if shares else None
