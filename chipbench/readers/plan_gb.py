"""What the compiled step holds on one device, from
``compiled.memory_analysis()``: arguments plus temporaries, in GB."""


def read(ctx):
    return (ctx["plan"]["argument"] + ctx["plan"]["temp"]) / 1e9
