"""The routed layer's grouped matmuls in the trace, whichever kernel runs
them: the least time the chip could take for their FLOPs and bytes
(forward and both backward products; ``grouped_matmul_cost`` of the
architecture's accounting module, from the configuration's and the
traffic's shapes) over the time they took, in percent. None where the
trace holds no such call.

A call is told by its instruction's name (PERF.md §3): the compiler's own
kernel for ``lax.ragged_dot`` is ``ragged-dot-*`` (its ``-metadata`` calls
are not products and are left out), JAX's Pallas kernels are ``gmm.N``
(forward, and to the rows) and ``tgmm.N`` (to the weights). So a trace of a
program on either kernel reads its own share, on one yardstick.
"""
import importlib
import re

from chipbench import flops

_GROUPED = re.compile(r"^%?(ragged-dot-(?!metadata)|t?gmm(\.\d+)?( = |$))")


def read(ctx):
    trace = ctx["trace"]
    if not trace:
        return None
    seconds = sum(spent for name, spent in trace["per_op_s"].items()
                  if _GROUPED.match(name))
    if not seconds:
        return None
    model, traffic = ctx["model"], ctx["traffic"]
    tokens = traffic["batch"] * traffic["seq"] // ctx["chips"]
    needed, moved = importlib.import_module(
        ctx["accounting"]).grouped_matmul_cost(model, tokens)
    least = flops.least_seconds(needed, moved, ctx["peaks"])[0]
    return 100.0 * least * model["layers"] * trace["steps"] / seconds
