"""Tokens a second a chip: whole steps completed in the window, times the
tokens of a step, over the span those steps took on the worker's clock,
over the chips. The window ends at a step boundary, so no partial step is
counted."""


def read(ctx):
    steps = ctx["counters"]["steps"]
    tokens = steps * ctx["traffic"]["batch"] * ctx["traffic"]["seq"]
    return tokens / ctx["clock"]["window_s"] / ctx["chips"]
