"""Device-busy time inside one execution of a named program, median
over the traced executions (all chips)."""


def read(ctx, programs):
    return ctx["trace"].program_device_ms(programs)
