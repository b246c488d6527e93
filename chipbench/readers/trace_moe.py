"""The routed layer in the trace: device time of the router, the sort, the
gather, the grouped matmuls and the combine over the device's busy time, in
percent; None where the trace holds none of them. (The grouped matmuls'
share of their roofline is ``trace_grouped``'s, whichever kernel runs
them.)

How an operation is told (PERF.md §3): the event's text is the HLO
instruction and carries no ``jax.named_scope``. The grouped matmuls are the
compiler's ``ragged-dot-*`` custom calls, found by that name. The rest of
the routed layer is found by shape, as ``flops.flash_call_cost`` finds the
flash kernels: an operation belongs to it when its result or an operand
has tokens·top_k rows (the sorted assignments and everything gathered by
them), is a [tokens…, experts] or [tokens…, top_k] array (router logits,
probabilities, gates), or when it PRODUCES a compute-dtype copy of the
stacked expert weights ([experts, a, b] in bf16, leading 1s aside: the
casts and transposes the products read). The optimizer's pass over the
expert weights reads their bf16 gradient and produces float32: not counted.
"""
import math
import re

from chipbench.trace_reduce import _parse

_SHAPE = re.compile(r"\b(pred|s32|u32|bf16|f32)\[([\d,]+)\]")
_GROUPED = re.compile(r"^%?ragged-dot-(?!metadata)")
_METADATA = re.compile(r"^%?ragged-dot-metadata")


def _shapes(text: str) -> list:
    return [(dtype, tuple(int(n) for n in dims.split(",")))
            for dtype, dims in _SHAPE.findall(text)]


def _is_routed(text: str, tokens: int, experts: int, top_k: int) -> bool:
    if _GROUPED.match(text) or _METADATA.match(text):
        return True
    for _, dims in _shapes(text):
        if dims[0] == tokens * top_k:
            return True
        if (len(dims) >= 2 and dims[-1] in (experts, top_k)
                and math.prod(dims[:-1]) == tokens):
            return True
    parsed = _parse(text)
    for dtype, dims in _shapes(parsed[1]) if parsed else ():
        while len(dims) > 3 and dims[0] == 1:
            dims = dims[1:]
        if dtype == "bf16" and len(dims) == 3 and dims[0] == experts:
            return True
    return False


def read(ctx):
    trace = ctx["trace"]
    if not trace:
        return None
    model, traffic = ctx["model"], ctx["traffic"]
    tokens = traffic["batch"] * traffic["seq"] // ctx["chips"]
    seconds = sum(
        spent for name, spent in trace["per_op_s"].items()
        if _is_routed(name, tokens, model["num_experts"],
                      model["num_experts_per_tok"]))
    return 100.0 * seconds / trace["busy_s"] if seconds else None
