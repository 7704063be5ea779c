"""Least time for the bytes a train step must move (distinct rows x
row bytes x read+write of table and accumulator, plus the batch) at
the device's peak bandwidth, over the step's device time, in percent:
says how far from bytes-bound the step is."""

from benchmarks import peaks
from benchmarks.readers import telemetry_window


def read(ctx, programs):
    ms = ctx["trace"].program_device_ms(programs)
    if ms is None or "distinct_rows_per_step" not in ctx:
        return None
    h2d = telemetry_window.read(ctx, "train/h2d_bytes", "train/steps") or 0.0
    chips = ctx["chips"]
    least = peaks.train_step_min_bytes(
        ctx["distinct_rows_per_step"] / chips, ctx["row_dim"], h2d / chips)
    return peaks.roofline_share_pct(least, ms / 1e3, ctx["device_kind"])
