"""A set-up interval the benchmark stamped with its own clock."""


def read(ctx, key):
    return ctx["setup"].get(key)
