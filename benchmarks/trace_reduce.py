"""From a profiler trace (.xplane.pb) to numbers: device busy time,
device time inside one execution of a named program, the operations
that took most time, and the longest idle gaps by what the host was
doing. Reads the file with jax.profiler.ProfileData and nothing else.

Planes named ``/device:...`` are chips: their "XLA Ops" line holds
the operations and "XLA Modules" the executions of whole programs. A
trace with no such plane is an error, unless the caller says it comes
from a CPU backend (``host_ops``: rehearsals and tests only): then its
operations sit on host thread lines, told by their ``hlo_op`` stat,
and a program execution is the span of one (hlo_module, run_id)."""

from __future__ import annotations

import dataclasses
import re
import statistics
from typing import Dict, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]


def union_length(intervals: Sequence[Interval]) -> float:
    total, end = 0.0, float("-inf")
    for a, b in sorted(intervals):
        if b <= end:
            continue
        total += b - max(a, end)
        end = b
    return total


def merged(intervals: Sequence[Interval]) -> List[Interval]:
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


@dataclasses.dataclass
class Op:
    name: str
    start: float            # seconds
    end: float
    stats: dict


@dataclasses.dataclass
class DeviceTrace:
    name: str
    ops: List[Op]
    modules: List[Op]


_RUN = re.compile(r"\(\d+\)$")


def program_name(module_event_name: str) -> str:
    """'jit_fm_train_step(1234)' -> 'fm_train_step'."""
    n = _RUN.sub("", module_event_name)
    return n[4:] if n.startswith("jit_") else n


@dataclasses.dataclass
class Trace:
    devices: List[DeviceTrace]
    host: List[Tuple[str, Op]]          # (thread line, event)
    t_first: float
    t_last: float

    @property
    def window_s(self) -> float:
        return self.t_last - self.t_first

    @property
    def busy_s(self) -> float:
        """Seconds in which an operation ran, averaged over devices."""
        per = [union_length([(o.start, o.end) for o in d.ops])
               for d in self.devices]
        return sum(per) / len(per)

    def program_runs(self, programs: Sequence[str]) -> List[Tuple[Op, float]]:
        """(execution, device-busy seconds inside it) of every whole
        execution of the named programs, all devices."""
        out = []
        for d in self.devices:
            spans = merged([(o.start, o.end) for o in d.ops])
            for m in d.modules:
                if program_name(m.name) not in programs:
                    continue
                busy = sum(min(b, m.end) - max(a, m.start)
                           for a, b in spans
                           if b > m.start and a < m.end)
                out.append((m, busy))
        return out

    def program_device_ms(self, programs: Sequence[str]) -> Optional[float]:
        runs = self.program_runs(programs)
        if not runs:
            return None
        return 1e3 * statistics.median(b for _, b in runs)

    def op_seconds(self) -> Dict[str, float]:
        tot: Dict[str, float] = {}
        for d in self.devices:
            for o in d.ops:
                tot[o.name] = tot.get(o.name, 0.0) + (o.end - o.start)
        return {k: v / len(self.devices) for k, v in tot.items()}

    def idle_gaps(self) -> List[Tuple[str, float]]:
        """Idle gaps of the first device, each named after the host
        event that covers most of it (none: the host was in Python
        between runtime calls), summed by name."""
        d = self.devices[0]
        busy = merged([(o.start, o.end) for o in d.ops])
        gaps, prev = [], self.t_first
        for a, b in busy:
            if a > prev:
                gaps.append((prev, a))
            prev = max(prev, b)
        if self.t_last > prev:
            gaps.append((prev, self.t_last))
        host = sorted(((e.start, e.end, e.name) for _, e in self.host))
        by: Dict[str, float] = {}
        import bisect
        starts = [h[0] for h in host]
        for a, b in gaps:
            best, cover = "python_between_runtime_calls", 0.0
            i = bisect.bisect_left(starts, a)
            for s, e, name in host[max(0, i - 64):i + 64]:
                c = min(e, b) - max(s, a)
                if c > cover:
                    best, cover = name, c
            if cover < 0.5 * (b - a):
                best = "python_between_runtime_calls"
            by[best] = by.get(best, 0.0) + (b - a)
        return sorted(by.items(), key=lambda kv: -kv[1])

    def breakdown(self) -> dict:
        ops = sorted(self.op_seconds().items(), key=lambda kv: -kv[1])
        return {"device_ops": [[k[:80], v] for k, v in ops[:10]],
                "idle_gaps": [[k[:80], v] for k, v in self.idle_gaps()[:10]]}


def _events(line):
    for e in line.events:
        yield Op(e.name, e.start_ns * 1e-9,
                 (e.start_ns + e.duration_ns) * 1e-9, dict(e.stats))


def reduce(path: str, host_ops: bool = False) -> Trace:
    import warnings
    from jax.profiler import ProfileData
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        data = ProfileData.from_file(path)
        devices, host = [], []
        for plane in data.planes:
            if plane.name.startswith("/device:"):
                ops, mods = [], []
                for line in plane.lines:
                    if line.name == "XLA Ops":
                        ops = list(_events(line))
                    elif line.name == "XLA Modules":
                        mods = list(_events(line))
                if ops:
                    devices.append(DeviceTrace(plane.name, ops, mods))
            elif plane.name.startswith("/host:CPU"):
                for line in plane.lines:
                    for e in _events(line):
                        host.append((line.name, e))
    if not devices and not host_ops:
        raise ValueError(
            f"the trace {path} holds no /device: plane with operations: "
            "the profiler lost the chip's side of the window")
    if not devices:
        # CPU backend: operations sit on host threads.
        ops = [e for _, e in host if "hlo_op" in e.stats]
        host = [(ln, e) for ln, e in host if "hlo_op" not in e.stats
                and e.end > e.start]
        runs: Dict[tuple, List[Op]] = {}
        for o in ops:
            runs.setdefault((o.stats.get("hlo_module"),
                             o.stats.get("run_id")), []).append(o)
        mods = [Op(str(k[0]), min(o.start for o in v),
                   max(o.end for o in v), {}) for k, v in runs.items()]
        if ops:
            devices.append(DeviceTrace("/host:CPU (no device plane)", ops,
                                       mods))
    if not devices:
        raise ValueError(f"no operation ran on a device in {path}")
    # The traced window is the devices' own: first operation seen to
    # last. The profiler starts and stops in the middle of the work,
    # and what the host did before the first recorded operation was
    # not idle time of the device.
    every = [o for d in devices for o in d.ops]
    return Trace(devices, host, min(o.start for o in every),
                 max(o.end for o in every))


def describe(path: str, limit: int = 6) -> str:
    """What a trace holds, for a look by hand (guide, section 6)."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    out = []
    for plane in data.planes:
        out.append(f"PLANE {plane.name}")
        for line in plane.lines:
            evs = list(line.events)
            out.append(f"  LINE {line.name}: {len(evs)} events")
            for e in evs[:limit]:
                out.append(f"    {e.name[:90]} start={e.start_ns} "
                           f"dur={e.duration_ns} stats="
                           f"{ {k: str(v)[:120] for k, v in e.stats} }")
    return "\n".join(out)


if __name__ == "__main__":
    import sys
    print(describe(sys.argv[1], int(sys.argv[2]) if len(sys.argv) > 2
                   else 6))
