"""Seconds between two stamps of the run's wall clock (``time.time()``; the
parent and its worker share the machine's clock)."""


def read(ctx, start, end):
    return ctx["clock"][end] - ctx["clock"][start]
