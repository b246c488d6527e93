"""Milliseconds a step of one kind of activity, by the program's own
stamps: the median over the window's steps of what the step-anatomy ring
(``ray_tpu._private.step_anatomy.local_records()``, this process's) holds
of ``kind`` under each step's id.

The train worker opens step 1 when the train function starts and
``session.report`` closes a step, so the window's first step shares id 1
with the warm-ups and their batches: the median is over ids 2…N, N the
steps the window made. ``data_wait`` is the streaming iterator's own stamp
around the ``next()`` it blocks in (``stamp_wait``): the time the consumer
really waited, without the benchmark's generator frames around it.

A number, or ``LookupError`` where the window has no second step or the
ring has lost records.
"""
import numpy as np


def read(ctx, kind):
    from ray_tpu._private import step_anatomy

    steps = ctx["counters"]["steps"]
    records = step_anatomy.local_records()
    if steps < 2 or records["activities_dropped"]:
        raise LookupError(
            f"no {kind!r} activity to read: {steps} step(s) in the window, "
            f"{records['activities_dropped']} record(s) dropped")
    seconds = dict.fromkeys(range(2, steps + 1), 0.0)
    for act in records["activities"]:
        if act["kind"] == kind and act["step_id"] in seconds:
            seconds[act["step_id"]] += act["end"] - act["start"]
    return 1e3 * float(np.median(list(seconds.values())))
