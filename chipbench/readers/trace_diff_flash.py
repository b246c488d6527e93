"""The DIFFERENTIAL-ATTENTION flash calls in the trace: the Pallas kernels
of ``ops/flash_attention.py`` as a differential layer calls them — q and k
at the head size, v at twice it, two calls a layer (``a¹``, ``a²``), with a
window (``flash_window_fwd`` / ``_dq`` / ``_dkv``) or without (``flash_fwd``
/ ``flash_dq`` / ``flash_dkv``) — found as the instructions of those names
that lie under the program's scope ``diff_flash`` (the scope table of
``readers/trace_scope.py``). ``what="share"``: their time over the device's
busy time. ``what="roofline"``: the least time the chip could take for each
call's FLOPs AT THE OPERANDS' OWN WIDTHS AND AT THE AREA ITS MASK KEEPS and
its bytes (``diff_flash_cost`` of the architecture's accounting module: a
windowed call at ``Σ min(t + 1, W)`` pairs, not the causal half that
``flops.flash_call_cost`` gives every call) over the time they took. Both in
percent.

None without a trace, without a table, where the accounting has no
``diff_flash_cost`` and where the trace holds no such call under the scope
(a CPU run, a program without the layer). A roofline over 105 is REFUSED,
not reported: the work is then counted too high.
"""
import importlib
import re

from chipbench import flops
from chipbench.readers import trace_scope

SCOPE = "diff_flash"
_CALL = re.compile(r"^%?\w*?flash_(window_)?(fwd|dq|dkv)[\w.]*$")


def read(ctx, what):
    trace = ctx["trace"]
    if not trace:
        return None
    cost = getattr(importlib.import_module(ctx["accounting"]),
                   "diff_flash_cost", None)
    table = trace_scope._table() if cost else None
    if table is None:
        return None
    model, traffic = ctx["model"], ctx["traffic"]
    seconds = least = 0.0
    for text, spent in trace["per_op_s"].items():
        name = text.partition(" = ")[0].strip().lstrip("%")
        call = _CALL.match(name)
        if not call or SCOPE not in table.get(name, ((), None))[0]:
            continue
        seconds += spent
        needed, moved = cost(model, call.group(2), bool(call.group(1)),
                             traffic["batch"] // ctx["chips"],
                             traffic["seq"])
        least += (flops.least_seconds(needed, moved, ctx["peaks"])[0]
                  * trace["per_op_calls"][text])
    if not seconds:
        return None
    if what == "share":
        return 100.0 * seconds / trace["busy_s"]
    share = 100.0 * least / seconds
    if share > 105.0:
        raise ValueError(
            f"the differential flash calls read {share:.1f} % of their "
            f"roofline ({least:.6f} s least, {seconds:.6f} s): the work is "
            f"counted too high")
    return share
