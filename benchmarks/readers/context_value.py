"""A number the driver worked out beside the end-to-end metric and put
in the readers' context (the median reading, for one)."""


def read(ctx, key):
    return ctx.get(key)
