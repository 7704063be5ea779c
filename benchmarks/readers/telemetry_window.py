"""Counters of the program's own telemetry stream (metrics_file),
taken as the difference between the snapshots at the window's first
and last sync points."""


from benchmarks.harness import read_telemetry


def window_delta(ctx, counter):
    s0, s1 = ctx["window_steps"]
    if "telemetry" not in ctx:
        ctx["telemetry"] = read_telemetry(ctx["telemetry_path"])
    snaps = {e["step"]: e["counters"] for e in ctx["telemetry"]
             if e.get("event") == "metrics" and "counters" in e}
    if s0 not in snaps or s1 not in snaps:
        return None
    a, b = snaps[s0].get(counter), snaps[s1].get(counter)
    if a is None or b is None:
        return None
    return b - a


def read(ctx, counter, over):
    """``counter`` over ``over``: another counter, or "wall_pct" for
    percent of the window's wall time."""
    if "window_steps" not in ctx:
        return None
    num = window_delta(ctx, counter)
    if num is None:
        return None
    if over == "wall_pct":
        return 100.0 * num / ctx["window_wall_s"]
    den = window_delta(ctx, over)
    return num / den if den else None
