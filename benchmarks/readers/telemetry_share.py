"""One counter of the program's telemetry stream as a share of
another over the window, in percent (``telemetry_window.read`` gives
the plain ratio)."""

from benchmarks.readers import telemetry_window


def read(ctx, counter, of):
    ratio = telemetry_window.read(ctx, counter, of)
    return None if ratio is None else 100.0 * ratio
