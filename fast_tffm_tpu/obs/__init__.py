"""obs/ — unified run telemetry (ISSUE 2) + timeline/health (ISSUE 3).

A dependency-free metrics registry (counters, gauges, fixed-bucket
histograms), a buffered JSONL sink that follows the same sync-safety
discipline as ``utils/summaries.ScalarSummaries`` (device scalars are
buffered and bulk-fetched only at epoch/flush barriers, never per
step), and the per-run wiring that lets every stage — data pipeline,
train loop, predict sweep, lockstep sharded path — feed one merged
event stream without threading a telemetry handle through every
signature.

On top of the aggregates, the timeline/health layer: ``trace.span``
brackets one stage into the same stream (export to Perfetto with
``tools/fmtrace``); ``health.Watchdog`` detects stalled runs via a
per-step heartbeat, dumps all-thread stacks, and flags non-finite loss
at the barrier fetch; driver crashes write a final forensic event with
the traceback and the sink's recent-event ring.

Off by default: everything here is a no-op until a driver activates a
``RunTelemetry`` (``metrics_file`` config knob; ``trace_spans`` and
``watchdog_stall_seconds`` gate the timeline/health layer). ``active()``
is the one lookup instrumented code paths make; when no run is active
it returns None and the instrumented site costs one global read.

Summarize or tail the resulting file with ``python -m tools.fmstat``.
"""

from fast_tffm_tpu.obs.health import Watchdog
from fast_tffm_tpu.obs.registry import (Counter, Gauge, Histogram,
                                        MetricsRegistry)
from fast_tffm_tpu.obs.sink import JsonlSink, read_events
from fast_tffm_tpu.obs.telemetry import (RunTelemetry, activate, active,
                                         make_telemetry, run_meta)
from fast_tffm_tpu.obs.trace import span

__all__ = [
    "Counter", "Gauge", "Histogram", "MetricsRegistry",
    "JsonlSink", "read_events",
    "RunTelemetry", "activate", "active", "make_telemetry", "run_meta",
    "Watchdog", "span",
]
