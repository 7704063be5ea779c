"""Least time for the bytes a score call must move whatever implements
it (``score_bytes.score_call_min_bytes``: the batch's distinct rows
read once, its real cells, its scores) at the device's peak bandwidth,
over the device time of one execution of the score program, in
percent. The driver puts the call's shape in the context
(``score_call``: what its probed calls were fed); a driver that probes
no score call, or a trace with no execution of the programs, reads
nothing."""

from benchmarks import peaks, score_bytes


def read(ctx, programs):
    ms = ctx["trace"].program_device_ms(programs)
    call = ctx.get("score_call")
    if ms is None or not call:
        return None
    least = score_bytes.score_call_min_bytes(
        call["distinct_rows"], ctx["row_dim"], call["cells"],
        call["examples"])
    return peaks.roofline_share_pct(least, ms / 1e3, ctx["device_kind"])
