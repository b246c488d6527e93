"""A routed layer that holds a SHARE of its experts, in the trace (the
``nemotron_h`` configurations: ``n_routed_experts`` held of the
``published`` number the router scores, a shared expert beside them).
``what="routed_share"``: device time of the routed layers' operations —
router, sort, gather, the held experts' grouped products and the copies of
their weights, combine, the shared expert — over the device's busy time.
``what="gmm_share"``: device time of the grouped products alone over the
busy time. Both in percent; None where the trace holds no such operation.

Why a share and no roofline for the products: the rows that reach the held
experts are the ROUTING's, between none and every assignment from one step
to the next (PERF.md §6, PR 34), and neither the trace nor the harness's
context says how many they were; the cost at the routing's expectation
over the time of a step that routed next to nothing there reads several
times 100 %.

How an operation is told (PERF.md §3): the event's text is the HLO
instruction and carries no ``jax.named_scope``. The grouped products are
JAX's Pallas kernels ``gmm.N`` / ``tgmm.N`` or the compiler's
``ragged-dot-*``, found by name as ``trace_grouped`` finds them; the rest
by shape, 1-sized axes aside, as ``trace_moe`` does for a layer that holds
every expert: an array whose leading axis is tokens·top_k (the sorted
assignments and everything gathered by them), a ``[tokens, scored]`` or
``[tokens, top_k]`` array (scores, gates), a TWO-axis array one of whose
axes is the shared expert's width, and an operation that PRODUCES a
compute-dtype copy of the held experts' weights (bf16 ``[held, hidden, w]``
or ``[held, w, hidden]``, ``w`` the expert's width or its next multiple of
128: the program pads the copy where the kernel's tiles ask for it). The
optimizer's pass reads a layer's gradient in those very shapes; it is told
by the moments it reads besides (an operand of the step's ``opt_state``)
and is not counted.
"""
import math
import re

from chipbench.trace_reduce import _parse

_SHAPE = re.compile(r"\b(pred|s32|u32|bf16|f32)\[([\d,]+)\]")
# the products themselves (as `trace_grouped` has them), and with the
# compiler's `-metadata` calls beside them
_PRODUCT = re.compile(r"^%?(ragged-dot-(?!metadata)|t?gmm(\.\d+)?( = |$))")
_GROUPED = re.compile(r"^%?(ragged-dot-|t?gmm(\.\d+)?( = |$))")


def _arrays(text: str) -> list:
    """Every array in the text as (dtype, axes), the 1-sized axes dropped."""
    return [(dtype, tuple(n for n in map(int, dims.split(",")) if n != 1))
            for dtype, dims in _SHAPE.findall(text)]


def _sizes(model: dict, tokens: int) -> dict:
    width = model["moe_intermediate_size"]
    return {"tokens": tokens, "top_k": model["num_experts_per_tok"],
            "scored": model["published"]["n_routed_experts"],
            "held": model["n_routed_experts"], "d": model["hidden_size"],
            "widths": (width, -(-width // 128) * 128),
            "shared": model["moe_shared_expert_intermediate_size"]}


def _is_routed(text: str, s: dict) -> bool:
    if _GROUPED.match(text):
        return True
    if "opt_state" in text:
        return False
    for _, axes in _arrays(text):
        if axes and axes[0] == s["tokens"] * s["top_k"]:
            return True
        if (len(axes) >= 2 and axes[-1] in (s["scored"], s["top_k"])
                and math.prod(axes[:-1]) == s["tokens"]):
            return True
        if len(axes) == 2 and s["shared"] in axes:
            return True
    parsed = _parse(text)
    for dtype, axes in _arrays(parsed[1]) if parsed else ():
        if (dtype == "bf16" and len(axes) == 3 and axes[0] == s["held"]
                and s["d"] in axes[1:]
                and any(w in axes[1:] for w in s["widths"])):
            return True
    return False


def read(ctx, what):
    trace = ctx["trace"]
    if not trace:
        return None
    model, traffic = ctx["model"], ctx["traffic"]
    if "published" not in model:
        return None
    sizes = _sizes(model, traffic["batch"] * traffic["seq"] // ctx["chips"])
    if what == "gmm_share":
        seconds = sum(spent for name, spent in trace["per_op_s"].items()
                      if _PRODUCT.match(name))
    else:
        seconds = sum(spent for name, spent in trace["per_op_s"].items()
                      if _is_routed(name, sizes))
    return 100.0 * seconds / trace["busy_s"] if seconds else None
