"""The Pallas flash-attention calls in the trace (told apart by
``flops.flash_call_cost``). ``what="share"``: their time over the device's
busy time. ``what="roofline"``: the least time the chip could take for the
calls' FLOPs and bytes over the time they took; the job's ``trace_notes``
say which bound applies to each kernel. Both in percent.

The calls the program names ``flash_window_*`` have the same signature and
are NOT read here: ``flash_call_cost`` would count them at the whole causal
area, and ``trace_window`` reads them at the area their window keeps
(counted twice, the pair read 138.8 % of the roofline in
``smallthinker4l-b1s16k``: ledger, PR 61)."""
from chipbench import flops
from chipbench.readers.trace_window import _CALL as _WINDOWED


def read(ctx, what):
    trace = ctx["trace"]
    if not trace:
        return None
    seconds = least = 0.0
    for name, spent in trace["per_op_s"].items():
        cost = flops.flash_call_cost(name)
        if cost and not _WINDOWED.match(name):
            seconds += spent
            least += (flops.least_seconds(cost[1], cost[2], ctx["peaks"])[0]
                      * trace["per_op_calls"][name])
    if not seconds:
        return None
    if what == "share":
        return 100.0 * seconds / trace["busy_s"]
    return 100.0 * least / seconds
