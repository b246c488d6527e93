"""Device time of one step from the trace: the union of the intervals in
which an operation ran inside one execution of the step program, median
over the traced steps, on the slowest chip."""
import numpy as np


def read(ctx):
    if not ctx["trace"]:
        return None
    return max(float(np.median(d["step_busy_ns"])) / 1e6
               for d in ctx["trace"]["devices"].values())
