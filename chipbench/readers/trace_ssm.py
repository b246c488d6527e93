"""The Mamba-2 mixers in the trace. ``what="mixer_share"``: device time of
the mixers' operations (in-projection, conv, state-space scan, gate and
norm, out-projection, forward, recomputed and backward) over the device's
busy time. ``what="ssd_roofline"``: the least time the chip could take for
the state-space scan's FLOPs and bytes (``ssd_cost`` of the architecture's
accounting module: ONE execution of one layer's scan) times the executions
the traced steps hold — a layer's forward, its recomputation where the
traffic remats, and its backward, each taken at the forward's cost — over
the time the scan's operations took. Both in percent; None where the trace
holds no such operation (a CPU run, a program without the mixer).

How an operation is told (PERF.md §3): the event's text is the HLO
instruction, results and operands with their shapes, and carries no
``jax.named_scope``. The program runs no kernel of its own for the scan, so
its operations are found by shape, leading 1s and 1-sized axes aside:

* the scan's: an array of four or more axes that ends in one of the chunked
  layouts — ``(…, r, P, N)`` chunk and carried states, ``(…, Q, Q)`` scores
  and decays, ``(…, g, r, P)`` chunked x, ``(…, Q, g, N)`` chunked B and C,
  ``(…, Q, g, r)`` / ``(…, g, r, Q)`` chunked Δ and log-decays (Q the chunk,
  g the groups, r the heads a group, P the head size, N the state). No
  other part of the program has such arrays: attention and the routed
  layer work on three axes or fewer, the flash kernels' row statistics
  ``[B·H, S/tile, 8, tile]`` end otherwise;
* the rest of a mixer: a TWO-axis array one of whose axes is the
  in-projection's width (z | xBC | dt) or the conv's (xBC), or that is
  ``[tokens, inner]``, ``[inner, hidden]`` or ``[tokens, g]``, and the
  gate-norm's ``[tokens, g, inner / g]``. The optimizer's pass reads a
  layer's gradient in those very shapes; it is told by the moments it reads
  besides (an operand of the step's ``opt_state``) and is not counted.
"""
import importlib
import re

from chipbench import flops

_SHAPE = re.compile(r"\b(pred|s32|u32|bf16|f32)\[([\d,]+)\]")


def _arrays(text: str) -> list:
    """Every array in the text as its axes, the 1-sized ones dropped."""
    return [tuple(n for n in map(int, dims.split(",")) if n != 1)
            for _, dims in _SHAPE.findall(text)]


def _sizes(model: dict, tokens: int) -> dict:
    heads, groups = model["mamba_num_heads"], model["n_groups"]
    inner = heads * model["mamba_head_dim"]
    conv = inner + 2 * groups * model["ssm_state_size"]
    return {"Q": model["chunk_size"], "g": groups, "r": heads // groups,
            "P": model["mamba_head_dim"], "N": model["ssm_state_size"],
            "inner": inner, "conv": conv, "in_proj": inner + conv + heads,
            "d": model["hidden_size"], "tokens": tokens}


def _is_scan(text: str, s: dict) -> bool:
    tails = {(s["r"], s["P"], s["N"]), (s["g"], s["r"], s["P"]),
             (s["Q"], s["g"], s["N"]), (s["Q"], s["g"], s["r"]),
             (s["g"], s["r"], s["Q"])}
    return any(len(axes) >= 4 and (axes[-3:] in tails
                                   or axes[-2:] == (s["Q"], s["Q"]))
               for axes in _arrays(text))


def _is_mixer(text: str, s: dict) -> bool:
    if _is_scan(text, s):
        return True
    if "opt_state" in text:
        return False
    flat = {(s["tokens"], s["inner"]), (s["inner"], s["d"]),
            (s["d"], s["inner"]), (s["tokens"], s["g"])}
    for axes in _arrays(text):
        if len(axes) == 2 and (axes in flat or s["in_proj"] in axes
                               or s["conv"] in axes):
            return True
        if axes == (s["tokens"], s["g"], s["inner"] // s["g"]):
            return True
    return False


def read(ctx, what):
    trace = ctx["trace"]
    if not trace:
        return None
    model, traffic = ctx["model"], ctx["traffic"]
    tokens = traffic["batch"] * traffic["seq"] // ctx["chips"]
    sizes = _sizes(model, tokens)
    match = _is_mixer if what == "mixer_share" else _is_scan
    seconds = sum(spent for name, spent in trace["per_op_s"].items()
                  if match(name, sizes))
    if not seconds:
        return None
    if what == "mixer_share":
        return 100.0 * seconds / trace["busy_s"]
    needed, moved = importlib.import_module(
        ctx["accounting"]).ssd_cost(model, tokens)
    least = flops.least_seconds(needed, moved, ctx["peaks"])[0]
    executions = model["pattern"].count("M") * (3 if traffic["remat"] else 2)
    return 100.0 * least * executions * trace["steps"] / seconds
