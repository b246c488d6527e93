"""Share of the traced window in which a collective ran on a device and no
compute did, on the worst chip, in percent. Left out where the trace holds
no collective at all (one chip)."""


def read(ctx):
    if not ctx["trace"]:
        return None
    devices = ctx["trace"]["devices"].values()
    if not any(d["collective_ns"] for d in devices):
        return None
    return 100.0 * max(d["collective_exposed_ns"] / d["window_ns"]
                       for d in devices)
