"""A count the job took."""


def read(ctx, name):
    return ctx["counters"][name]
