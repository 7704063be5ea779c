"""Share of the traced window in which the first chip is idle and no
runtime call of the host covers the gap: Python between calls of the
scorer (parse, build, fetch, write)."""


def read(ctx):
    t = ctx["trace"]
    gaps = dict(t.idle_gaps())
    return 100.0 * gaps.get("python_between_runtime_calls", 0.0) / t.window_s
