"""What every driver shares: finding a cell's files by name, the
process clock, the program's config file, the device report, the
profiler window, the per-layer readers and the result line."""

from __future__ import annotations

import dataclasses
import glob
import importlib
import json
import os
import sys
import time
from typing import Any, Dict, List, Optional, Sequence

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH_DIR = os.path.join(ROOT, "benchmarks")
WORK_ROOT = os.path.join(ROOT, ".bench_work")


class RunFailed(Exception):
    """The run cannot give a result (no chip, deferred loss lines,
    too few readings...): exit non-zero, print no result line."""


def _load_json(path: str) -> dict:
    with open(path, "r", encoding="utf-8") as fh:
        return json.load(fh)


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: List[dict]
    per_layer: List[dict]

    @property
    def kind(self) -> str:
        return self.traffic["kind"]


def load_cell(workload: str, root: str = ROOT) -> Cell:
    """Everything a cell is made of, found by the names in
    BENCHMARK.json: its configuration file, its traffic file and the
    metrics it reports. Adding a cell is adding files and entries."""
    spec = _load_json(os.path.join(root, "BENCHMARK.json"))
    by_name = {w["name"]: w for w in spec["workloads"]}
    if workload not in by_name:
        raise RunFailed(f"no workload {workload!r} in BENCHMARK.json "
                        f"(known: {sorted(by_name)})")
    w = by_name[workload]
    cfg_entry = next(c for c in spec["configs"] if c["name"] == w["config"])
    config = _load_json(os.path.join(root, cfg_entry["file"]))
    traffic = _load_json(os.path.join(
        root, "benchmarks", "traffic", w["traffic"] + ".json"))

    def mine(m):
        return "workloads" not in m or workload in m["workloads"]

    e2e = [m for m in spec["end_to_end"] if mine(m)]
    e2e_names = {m["name"] for m in e2e}
    layer = [m for m in spec["per_layer"]
             if mine(m) and m["moves"] in e2e_names]
    return Cell(name=workload, chips=int(w["chips"]), config=config,
                traffic=traffic, end_to_end=e2e, per_layer=layer)


@dataclasses.dataclass
class Run:
    cell: Cell
    seed: int
    seconds: float
    trace: bool
    rehearse: bool
    t0: float                                  # process start, monotonic
    setup: Dict[str, float] = dataclasses.field(default_factory=dict)

    @property
    def work_dir(self) -> str:
        return os.path.join(WORK_ROOT, self.cell.name)

    def since_start(self) -> float:
        return time.monotonic() - self.t0

    @property
    def program_seed(self) -> int:
        # The program adds the epoch to its seed and hands it to
        # 32-bit generators; the corpus and weights use the full seed.
        return int(self.seed) % (2 ** 31 - 2 ** 20)


def say(*parts) -> None:
    print(*parts, flush=True)


def open_devices(run: Run):
    """Import jax, reach the backend, refuse anything but the chips the
    cell asks for (a rehearsal runs on whatever is there and prints no
    metric under a device name)."""
    t = time.monotonic()
    import jax
    run.setup["import_jax_s"] = time.monotonic() - t
    devices = jax.devices()
    run.setup["setup_start_s"] = run.since_start()
    d = devices[0]
    info = {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices)}
    if not run.rehearse:
        if d.platform != "tpu":
            raise RunFailed(f"no accelerator: jax found {info}")
        if len(devices) != run.cell.chips:
            raise RunFailed(
                f"cell {run.cell.name} asks for {run.cell.chips} chip(s) "
                f"and jax found {len(devices)}: the program spans every "
                "chip it sees, so the count must match")
        from benchmarks.peaks import peaks_for
        peaks_for(d.device_kind)            # unknown device: an error
    return info


def enable_cache() -> str:
    """The program's own persistent-cache policy: the directory
    JAX_COMPILATION_CACHE_DIR names, else <checkout>/.jax_cache."""
    from fast_tffm_tpu.compile_cache import enable_compilation_cache
    return enable_compilation_cache()


def program_cfg(config: dict, extra: Dict[str, Dict[str, Any]],
                work_dir: str):
    """The configuration as a user writes it: an INI file read by the
    program's own load_config, ``extra`` keys added to its sections."""
    sections = {k: dict(v) for k, v in config["program"].items()}
    for sec, kv in extra.items():
        sections.setdefault(sec, {}).update(kv)
    os.makedirs(work_dir, exist_ok=True)
    path = os.path.join(work_dir, "run.cfg")
    with open(path, "w", encoding="utf-8") as fh:
        for sec, kv in sections.items():
            fh.write(f"[{sec}]\n")
            for k, v in kv.items():
                if isinstance(v, (list, tuple)):
                    v = ",".join(str(x) for x in v)
                fh.write(f"{k} = {v}\n")
    from fast_tffm_tpu.config import apply_env_overrides, load_config
    return apply_env_overrides(load_config(path))


# What of the program's configuration defines the mathematics the
# reference follows; MODEL_APART is what of ModelSpec does not.
MODEL_FIELDS = ("model_type", "order", "factor_num", "field_num", "row_dim",
                "loss_type", "factor_lambda", "bias_lambda",
                "learning_rate", "adagrad_init")
MODEL_APART = ("vocabulary_size", "kernel", "dedup")


def model_of(cfg, config: dict) -> dict:
    """The one description of the model that the reference, the check
    and the control work from: the ``FmConfig`` fields that define the
    score (``model_type``, ``order``, ``factor_num``, ``field_num``,
    and ``row_dim``, the width they give a row), the loss
    (``loss_type``, ``factor_lambda``, ``bias_lambda``) and the update
    (``learning_rate``, ``adagrad_init``: sparse Adagrad over the
    touched rows), with the ``reference_family`` the configuration's
    file names. A field the program's step depends on and this drops is
    how a wrong reference passes unseen: tests/benchmarks holds the
    list against ``ModelSpec``, whose other fields (``MODEL_APART``)
    size the table or choose how the same mathematics is computed. A
    family that is not there fails here, before anything is timed."""
    from benchmarks import reference
    model = {k: getattr(cfg, k) for k in MODEL_FIELDS}
    model["reference_family"] = config.get("reference_family")
    try:
        reference.family_of(model)
    except (KeyError, ValueError) as e:
        raise RunFailed(e.args[0]) from None
    return model


def fresh_dir(path: str) -> str:
    import shutil
    shutil.rmtree(path, ignore_errors=True)
    os.makedirs(path)
    return path


class TraceWindow:
    """A profiler trace over the first ``seconds`` of the window."""

    def __init__(self, run: Run):
        self.dir = os.path.join(run.work_dir, "trace")
        self.seconds = float(run.cell.traffic.get("trace_seconds", 5.0))
        self.t_begin: Optional[float] = None
        self.active = False

    def start(self) -> None:
        import jax
        fresh_dir(self.dir)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self.t_begin = time.monotonic()
        self.active = True

    def due(self) -> bool:
        return (self.active
                and time.monotonic() - self.t_begin >= self.seconds)

    def stop(self) -> None:
        if not self.active:
            return
        import jax
        jax.profiler.stop_trace()
        self.active = False

    def xplane(self) -> str:
        files = glob.glob(os.path.join(self.dir, "**", "*.xplane.pb"),
                          recursive=True)
        if not files:
            raise RunFailed(f"the profiler wrote no trace under {self.dir}")
        return max(files, key=os.path.getmtime)


def read_telemetry(path: str) -> List[dict]:
    events = []
    for p in sorted(glob.glob(path + "*")):
        if p.endswith((".quarantine", ".stacks")):
            continue
        with open(p, "r", encoding="utf-8") as fh:
            for line in fh:
                line = line.strip()
                if line:
                    events.append(json.loads(line))
    return events


def layer_metrics(run: Run, ctx: dict) -> Dict[str, dict]:
    """Each per-layer metric of the cell through its own reader
    (benchmarks/layer_metrics/<name>.json names it). A reader that
    finds nothing to read returns None and the metric is left out."""
    out = {}
    for m in run.cell.per_layer:
        spec = _load_json(os.path.join(BENCH_DIR, "layer_metrics",
                                       m["name"] + ".json"))
        reader = importlib.import_module(
            "benchmarks.readers." + spec["reader"])
        try:
            value = reader.read(ctx, **spec.get("args", {}))
        except KeyError:
            if not run.rehearse:    # e.g. a device with no published peaks
                raise
            value = None
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def memory_peak_bytes() -> int:
    import jax
    peaks = []
    for d in jax.local_devices():
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks) if peaks else 0


def _check_said(c: dict) -> str:
    return f"check {c['name']}: {c['value']!r} (limit {c['limit']!r})"


def print_checks(checks: List[dict]) -> bool:
    """Every number compared beside its limit; all must hold."""
    ok = True
    for c in checks:
        good = bool(c["value"] <= c["limit"])
        ok = ok and good
        say(f"{_check_said(c)} {'ok' if good else 'FAILED'}")
    return ok


def window_rate(run: Run, rd, t_start: float, cycle: int, per: int,
                what: str) -> dict:
    """The window's rates by the one reading rule, said on a line of
    their own; ``per`` divides them (chips)."""
    from benchmarks import readings
    try:
        rate = readings.rate_from_readings(rd, t_start,
                                           t_start + run.seconds, cycle)
    except ValueError as e:
        raise RunFailed(str(e)) from None
    for k in ("rate", "median", "min", "max"):
        rate[k] /= per
    say(f"setup: { {k: round(v, 3) for k, v in run.setup.items()} }")
    say(f"readings ({rate['n']} inside the window, {rate['dropped']} "
        f"outside), {what}: {[round(r / per, 1) for r in rate['rates']]}")
    say(f"all work over all time of the span of {rate['n']} readings, "
        f"{rate['n'] // cycle} cycles (the end-to-end rate) "
        f"{rate['rate']:.1f}; median reading (the per-layer "
        f"steady_rate) {rate['median']:.1f} min {rate['min']:.1f} max "
        f"{rate['max']:.1f}")
    return rate


def finish(run: Run, device: dict, end_to_end: Dict[str, float],
           checks: List[dict], check_seconds: float, attempted: int,
           failed: int, tracer: Optional[TraceWindow], ctx: dict) -> str:
    """What every driver does once the window has closed and the
    output is checked: the end-to-end metrics, which the driver gives
    by name (``setup_s`` is the harness's own stamp), or with --trace 1
    the per-layer metrics through their readers, and the result line."""
    peak = memory_peak_bytes()
    correct = print_checks(checks) and failed == 0
    say(f"output check took {check_seconds:.2f} s (outside the window "
        f"and setup_s); memory peak {peak} B")
    dev = dict(device, memory_peak_bytes=peak)
    breakdown = None
    if tracer is None:
        values = dict(end_to_end, setup_s=run.setup["setup_s"])
        missing = [m["name"] for m in run.cell.end_to_end
                   if m["name"] not in values]
        if missing:
            raise RunFailed(f"the {run.cell.kind} driver gave no value "
                            f"for {missing}")
        metrics = {m["name"]: {"value": float(values[m["name"]]),
                               "unit": m["unit"]}
                   for m in run.cell.end_to_end}
    else:
        from benchmarks import trace_reduce
        try:
            # Only a rehearsal may read operations off host threads: a
            # chip's trace without its device plane is a fault.
            trace = trace_reduce.reduce(tracer.xplane(),
                                        host_ops=run.rehearse)
        except ValueError as e:
            raise RunFailed(str(e)) from None
        ctx = dict(ctx, run=run, setup=run.setup, trace=trace,
                   device_kind=device["kind"],
                   chips=max(int(device["count"]), 1))
        metrics = layer_metrics(run, ctx)
        dev.update(busy_s=trace.busy_s, window_s=trace.window_s)
        breakdown = trace.breakdown()
        say(f"trace: window {trace.window_s:.3f} s, device busy "
            f"{trace.busy_s:.3f} s, idle share "
            f"{1 - trace.busy_s / trace.window_s:.4f}")
    return result_line(run, dev, correct, attempted, failed, metrics,
                       breakdown, checks)


def _plain(v):
    """A NumPy scalar is no JSON number."""
    return v.item() if hasattr(v, "item") else v


def result_line(run: Run, device: dict, correct: bool, attempted: int,
                failed: int, metrics: Dict[str, dict],
                breakdown: Optional[dict] = None,
                checks: Sequence[dict] = ()) -> str:
    """The run's last line of stdout. Each number compared stands
    beside its limit under ``check``, the line's last key, and on the
    last lines of stderr: what a record of a run that was not correct
    keeps."""
    say(f"metrics: {json.dumps(metrics)}")
    if run.rehearse:
        # A rehearsal proves the control flow; its numbers come from
        # whatever ran it and never stand under a device metric's name.
        metrics = {}
        device = dict(device, rehearsal=True)
    line = {"correct": bool(correct), "attempted": int(attempted),
            "failed": int(failed), "metrics": metrics, "device": device}
    if breakdown:
        line["breakdown"] = breakdown
    line["check"] = {c["name"]: {"value": _plain(c["value"]),
                                 "limit": _plain(c["limit"])}
                     for c in checks}
    for c in checks:
        print(_check_said(c), file=sys.stderr, flush=True)
    return json.dumps(line)
