"""An operator that the program runs under a named scope, against the
chip's peaks, in percent. The metric's file names the three things that
differ from one operator to the next (``args``):

  ``scope``  the program's named scope around the operator, as the scope
             table of ``readers/trace_scope.py`` has it (forward, recomputed
             and backward instructions alike carry the scope);
  ``cost``   the function of the architecture's accounting module that gives
             ONE execution of one layer's operator, forward and backward
             apart, as ``{"forward": (flops, bytes), "backward": (...)}`` —
             what the operator must do, and what it must read and write
             once at the filed dtypes;
  ``kind``   the layers that run it, as the accounting's ``layout`` names
             them.

The share is the least time the chip could take (each execution's FLOPs
over the bf16 peak or its bytes over the HBM peak in ``peaks.json``,
whichever is larger) times the executions the traced steps hold — a layer's
forward, its recomputation where the traffic remats, and its backward — over
the device time of the instructions under ``scope``. The same work whatever
implements it: a kernel that replaces a plain-JAX form is read by the same
scope. A new operator of this form is a metric file, not a reader.

None without a trace, without a table, where the architecture's accounting
has no such ``cost`` or the table no instruction under ``scope`` (a program
without the operator) and where none of them is in the trace. A share over
105 is REFUSED, not reported: the work is then counted too high, or the
scope leaves out part of it (a fusion carries its root's name: the
operator's element-wise ends fused into a neighbouring product would be
counted there).
"""
import importlib

from chipbench import flops
from chipbench.readers import trace_scope


def read(ctx, scope, cost, kind):
    accounting = ctx["trace"] and importlib.import_module(ctx["accounting"])
    if not accounting or not hasattr(accounting, cost):
        return None
    # the scope reader's own matching: the share of the busy time under it
    under = trace_scope.read(ctx, scopes=[scope])
    if not under:
        return None
    trace, model, traffic = ctx["trace"], ctx["model"], ctx["traffic"]
    seconds = under / 100.0 * trace["busy_s"]
    tokens = traffic["batch"] * traffic["seq"] // ctx["chips"]
    one = getattr(accounting, cost)(model, tokens)
    forward, backward = (flops.least_seconds(*one[k], ctx["peaks"])[0]
                         for k in ("forward", "backward"))
    layers = accounting.layout(model).count(kind)
    least = (forward * (2 if traffic["remat"] else 1) + backward) \
        * layers * trace["steps"]
    share = 100.0 * least / seconds
    if share > 105.0:
        raise ValueError(
            f"{cost} reads {share:.1f} % of its roofline ({least:.6f} s "
            f"least, {seconds:.6f} s under {scope!r}): the work is counted "
            f"too high, or the scope leaves out part of it")
    return share
