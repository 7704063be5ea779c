"""Driver of ``kind: train_bags`` traffic: ``drivers/train.py``'s run,
on a corpus whose lines have bags of tokens (corpus_bags.py), so that
a job's batches ship at more than one width and the job holds a step
program a width.

Nothing of ``train.run`` is copied. For the call, three names it looks
up are rebound, as its own ``Seams`` rebinds the program's: the corpus
generator (``corpus_bags.in_place_of_generate``), the probe
(``WidthProbe``) and ``harness.finish``, which gains one check line
and, in a traced run, the step's device time by width.

The probe goes on recording until a step at each of the traffic's
``checked_widths`` widths has been checked: at least ``checked_steps``,
at most ``checked_steps_most`` steps. It notes the width of every later
call (a shape, no copy). ``widths_shipped_not_checked`` counts the
widths the span's steps had that no checked step had: a program the
window ran and the reference never held."""

from __future__ import annotations

import collections
import json
import os
import statistics

from benchmarks import corpus_bags, harness, trace_reduce, xplane_meta
from benchmarks.drivers import train as train_driver
from benchmarks.harness import RunFailed, say
from benchmarks.readers import scope_device_ms

STEP_SPAN = "train/step"        # the program's span: stats step, width
CLOCK_SLACK_S = 0.002           # host and device stamps of one instant


class WidthProbe(train_driver.StepProbe):
    def __init__(self, floor: int, n_widths: int, most: int):
        super().__init__(most)      # n_check: lowered once the widths are in
        self.floor, self.n_widths = floor, n_widths
        self.widths = []            # of every call, in order

    def wrap(self, step):
        record = super().wrap(step)

        def probed(*args, **kwargs):
            self.widths.append(int(kwargs["local_idx"].shape[1]))
            done = self.calls + 1
            if (self.calls < self.n_check and done >= self.floor
                    and len(set(self.widths[:done])) >= self.n_widths):
                self.n_check = done         # this call is the last checked
            return record(*args, **kwargs)
        return probed

    def check(self, window_steps) -> dict:
        """Steps are counted from 1 and so are the calls: the span's
        steps are calls ``s_first + 1 .. s_last``."""
        s_first, s_last = window_steps
        span = collections.Counter(self.widths[s_first:s_last])
        checked = collections.Counter(self.widths[:self.calls])
        say(f"widths: {self.calls} checked steps at {dict(checked)}; the "
            f"span's {s_last - s_first} steps at {dict(span)}")
        return {"name": "widths_shipped_not_checked",
                "value": len(set(span) - set(checked)), "limit": 0}


def executions_by_width(trace, programs) -> dict:
    """{width: [DeviceTrace]}: the chips' operations with, as their
    executions, those of the named programs whose ``train/step`` span
    said that width. The trace ends on a loss line, where the host has
    waited for the device: the last span is the last execution's, and
    the ones before pair off backwards. {} (and a line that says what
    was found) where the spans carry no width (a program from before
    they did) or an execution started before its own dispatch did
    (``CLOCK_SLACK_S`` allows for the two clocks)."""
    spans = sorted((e for _, e in trace.host if e.name == STEP_SPAN
                    and "width" in e.stats), key=lambda e: e.start)
    out = {}
    for d in trace.devices:
        runs = sorted((m for m in d.modules if trace_reduce.program_name(
            m.name) in programs), key=lambda m: m.start)
        pairs = list(zip(reversed(spans), reversed(runs)))
        lags = [m.start - e.start for e, m in pairs]
        if not pairs or min(lags) < -CLOCK_SLACK_S:
            say(f"steps by width: left out: {len(spans)} {STEP_SPAN} spans "
                f"say a width, {len(runs)} executions on {d.name}, an "
                f"execution starts {min(lags, default=0.0):.6f} to "
                f"{max(lags, default=0.0):.6f} s after its span")
            return {}
        for e, m in pairs:
            out.setdefault(int(e.stats["width"]), {}).setdefault(
                d.name, trace_reduce.DeviceTrace(d.name, d.ops, [])
            ).modules.append(m)
    return {w: list(per.values()) for w, per in out.items()}


def say_steps_by_width(run, tracer) -> None:
    """A traced run's step by width: executions, device time, scopes.
    Lines to read, no metric: each scope metric is the median over
    both widths' executions. (The trace is reduced here a second time:
    ``harness.finish`` keeps its own reduction to itself.)"""
    with open(os.path.join(harness.BENCH_DIR, "layer_metrics",
                           "step_device_ms.json")) as fh:
        programs = json.load(fh)["args"]["programs"]
    path = tracer.xplane()
    trace = trace_reduce.reduce(path, host_ops=run.rehearse)
    by_width = executions_by_width(trace, programs)
    if not by_width:
        return
    meta = xplane_meta.read(path)
    for w, devices in sorted(by_width.items()):
        sub = trace_reduce.Trace(devices, [], trace.t_first, trace.t_last)
        busy = [b for _, b in sub.program_runs(programs)]
        scopes = scope_device_ms.by_scope(sub, meta, programs)
        say(f"steps at width {w}: {len(busy)} executions, device "
            f"{1e3 * statistics.median(busy):.3f} ms (min "
            f"{1e3 * min(busy):.3f}, max {1e3 * max(busy):.3f}); "
            + ", ".join(f"{s or 'unscoped'} {scopes[s][0]:.3f}"
                        for s in sorted(scopes, key=str)))


def run(run, device, breaker=None) -> str:
    tr = run.cell.traffic
    floor, most = int(tr["checked_steps"]), int(tr["checked_steps_most"])
    if int(tr["warmup_readings"]) * int(tr["steps_per_reading"]) < most:
        raise RunFailed("the warm-up must hold the most steps the probe "
                        "may check (checked_steps_most)")
    probes = []

    def make_probe(_n_check):
        probes.append(WidthProbe(floor, int(tr["checked_widths"]), most))
        return probes[-1]

    def finish(run_, device_, end_to_end, checks, *args, **kw):
        checks.append(probes[-1].check(kw["ctx"]["window_steps"]))
        if kw["tracer"] is not None:
            say_steps_by_width(run_, kw["tracer"])
        return kept_finish(run_, device_, end_to_end, checks, *args, **kw)

    kept_probe, kept_finish = train_driver.StepProbe, harness.finish
    train_driver.StepProbe, harness.finish = make_probe, finish
    try:
        with corpus_bags.in_place_of_generate():
            return train_driver.run(run, device, breaker)
    finally:
        train_driver.StepProbe, harness.finish = kept_probe, kept_finish
