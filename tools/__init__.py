"""Operational tooling: probes and the fmstat/fmlint/fmtrace CLIs.

A package (not loose scripts) so `python -m tools.fmstat` /
`python -m tools.fmlint` / `python -m tools.fmtrace` work from the
repo root — the standalone scripts (kernel_probe.py,
offload_smoke.py) still run directly as before.
"""

from typing import List, Sequence


def expand_stream_args(paths: Sequence[str]) -> List[str]:
    """Glob-expand metrics-file CLI args and fail loudly on unreadable
    inputs — the ONE argument policy for the stream-reading CLIs
    (fmstat, fmtrace), so their glob sorting and missing-file behavior
    can't drift apart. read_events itself tolerates only torn final
    lines; a typo'd path must error, not summarize zero events."""
    import glob as globlib

    from fast_tffm_tpu.obs.sink import read_events
    out: List[str] = []
    for p in paths:
        hits = sorted(globlib.glob(p))
        out.extend(hits if hits else [p])
    for f in out:
        next(iter(read_events(f)), None)
    return out
