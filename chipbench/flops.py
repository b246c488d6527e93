"""Operations and bytes, computed from shapes: what belongs to the chip and
to the program's kernels. Nothing here imports JAX or the program, and
nothing here knows a model: a model's parameters and FLOPs a token are in
its architecture's module, ``accounting/<reference>.py``.
"""
import json
import math
import os
import re

_PEAKS_FILE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "peaks.json")


def peaks_for(device_kind: str) -> dict:
    """The published peaks of one chip of this kind. A kind that is not in
    ``peaks.json`` is an error, never a default."""
    with open(_PEAKS_FILE) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(
            f"no peak FLOP/s on file for device kind {device_kind!r}; add "
            f"it to chipbench/peaks.json with its source")
    return table[device_kind]


def padded_vocab(vocab_size: int, multiple: int = 128) -> int:
    return -(-vocab_size // multiple) * multiple


# ------------------------------------------------------- flash kernels
_SHAPE = re.compile(r"\b(bf16|f32)\[([\d,]+)\]")
_BYTES = {"bf16": 2, "f32": 4}


def _shapes(text: str) -> list:
    return [(dtype, tuple(int(n) for n in dims.split(",")))
            for dtype, dims in _SHAPE.findall(text)]


def flash_call_cost(op_text: str):
    """(kind, flops, bytes) that one call of a flash-attention Pallas
    kernel needs, read from the HLO text the profiler gives the event, or
    None for any other operation.

    The three kernels of ``ops/flash_attention.py`` carry no name; they
    are told apart by their signature: the forward takes q, k, v and
    returns (o, lse); the backward kernels take q, k, v, do, lse, delta
    and return dq, or (dk, dv). Operands are [B·H, S, D]. FLOPs are the
    matmuls the kernel's own algorithm needs under a causal mask (half of
    S²): forward QK^T and PV; dq kernel QK^T, dO·V^T and dS·K; dk/dv
    kernel QK^T, dO·V^T, P^T·dO and dS^T·Q. Bytes are every operand read
    once and every result written once."""
    if 'custom_call_target="tpu_custom_call"' not in op_text:
        return None
    head, _, tail = op_text.partition(" custom-call(")
    results = _shapes(head.partition(" = ")[2])
    operands = _shapes(tail.partition('custom_call_target=')[0])
    if not results or len(operands) not in (3, 6):
        return None
    bh, s, d = operands[0][1]
    if len(operands) == 3:
        kind, matmuls = "fwd", 2
    elif len(results) == 1:
        kind, matmuls = "bwd_dq", 3
    else:
        kind, matmuls = "bwd_dkv", 4
    flops = matmuls * 2 * bh * s * s * d // 2
    moved = sum(_BYTES[t] * math.prod(shape)
                for t, shape in results + operands)
    return kind, flops, moved


def least_seconds(flops: float, moved_bytes: float, peaks: dict):
    """The least time the chip could take, and which bound sets it."""
    t_compute = flops / peaks["bf16_flops_per_s"]
    t_memory = moved_bytes / peaks["hbm_bytes_per_s"]
    return ((t_compute, "compute") if t_compute >= t_memory
            else (t_memory, "memory"))
