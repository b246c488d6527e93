"""From a profiler trace to numbers: device busy and idle time, time by
operation, collective time that no compute hides, and what the host was
doing in each idle gap.

The arithmetic works on plain ``Event`` tuples, so that it can be checked on
a hand-made list with known answers; ``load_xplane`` is the only function
that touches the profiler's file format (through ``jax.profiler.ProfileData``,
nothing else). What a v5e trace looks like (read by hand, PR 23): one plane
``/device:TPU:<n>`` a chip, with the lines ``XLA Modules`` (one event a
program execution, ``jit_step(<hash>)``), ``XLA Ops`` (every HLO operation,
NESTED: a ``%while`` event covers the events of its body) and ``Async XLA
Ops`` (a ``*-start`` event lasting until its ``*-done``: copies, slices and
collectives that run beside the compute stream). The event's name is the
HLO instruction's text. The host's ``TraceAnnotation`` spans are on the plane
``/host:CPU``, one line a thread, on the same clock to within about a
millisecond.
"""
import collections
import re

Event = collections.namedtuple("Event", "name start_ns end_ns")

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
_COLLECTIVES = ("all-reduce|all-gather|reduce-scatter|all-to-all|"
                "collective-permute|collective-broadcast")
_COLLECTIVE = re.compile(rf"^({_COLLECTIVES})(-start|-update|-done)?$")
# opcodes that only wrap a computation, and what the text names it by
_WRAPPER = re.compile(r"^async-(start|update|done)$")
_WRAPPED = re.compile(rf"^({_COLLECTIVES})")


# ------------------------------------------------------------- intervals

def union(intervals) -> list:
    """Sorted, disjoint (start, end) pairs covering the same instants."""
    merged = []
    for start, end in sorted(i for i in intervals if i[1] > i[0]):
        if merged and start <= merged[-1][1]:
            if end > merged[-1][1]:
                merged[-1] = (merged[-1][0], end)
        else:
            merged.append((start, end))
    return merged


def total(intervals) -> int:
    return sum(end - start for start, end in union(intervals))


def clip(intervals, lo, hi) -> list:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def subtract(intervals, holes) -> list:
    """The instants of ``intervals`` that no interval of ``holes`` covers."""
    out = []
    holes = union(holes)
    for start, end in union(intervals):
        at = start
        for h_start, h_end in holes:
            if h_end <= at:
                continue
            if h_start >= end:
                break
            if h_start > at:
                out.append((at, h_start))
            at = max(at, h_end)
        if at < end:
            out.append((at, end))
    return out


def self_segments(events) -> list:
    """Events of one line, possibly nested, as disjoint (name, start, end)
    segments: each instant belongs to the innermost event that covers it.
    So a loop's time is what its body leaves over, and summing segments
    never counts an instant twice."""
    segments, stack = [], []      # stack of [event, time accounted up to]

    def close(until):
        while stack and stack[-1][0].end_ns <= until:
            ev, at = stack.pop()
            if ev.end_ns > at:
                segments.append((ev.name, at, ev.end_ns))
            if stack:
                stack[-1][1] = max(stack[-1][1], ev.end_ns)

    for ev in sorted(events, key=lambda e: (e.start_ns, -e.end_ns)):
        close(ev.start_ns)
        if stack:
            parent, at = stack[-1]
            if ev.start_ns > at:
                segments.append((parent.name, at, ev.start_ns))
            stack[-1][1] = max(at, ev.start_ns)
        stack.append([ev, ev.start_ns])
    close(float("inf"))
    return sorted(segments, key=lambda s: s[1])


# ------------------------------------------------------------ operations

def _parse(text: str):
    """HLO instruction text -> (instruction name, result, opcode, what
    follows the opcode), or None where the text is no instruction."""
    instr, sep, rest = text.partition(" = ")
    if not sep:
        return None
    if rest.startswith("("):
        depth = 0
        for i, ch in enumerate(rest):
            depth += ch == "("
            depth -= ch == ")"
            if depth == 0:
                break
        result, rest = rest[:i + 1], rest[i + 1:]
    else:
        result, _, rest = rest.partition(" ")
    opcode, _, rest = rest.strip().partition("(")
    return instr.lstrip("%"), result, opcode, rest


def short_op_name(text: str, limit: int = 96) -> str:
    """``%fusion.217 = f32[50304,768]{1,0:T(8,128)} fusion(...), kind=kOutput``
    -> ``fusion.217 fusion:kOutput f32[50304,768]``: the instruction, what
    it is and what it produces, without layouts or operands."""
    parsed = _parse(text)
    if parsed is None:
        return text[:limit]
    instr, result, opcode, rest = parsed
    detail = re.search(r'custom_call_target="([^"]+)"|kind=(\w+)', rest)
    if detail:
        opcode += ":" + (detail.group(1) or detail.group(2))
    result = re.sub(r"\{[^}]*\}", "", result)
    return f"{instr} {opcode} {result}"[:limit]


def is_collective(text: str) -> bool:
    """By the instruction's opcode, ``-start``, ``-update`` and ``-done``
    forms included, not by its name: ``jax.lax.psum`` names its all-reduce
    ``psum.N``, and a fusion may be named after anything. Two opcodes only
    wrap a computation, and the text has nothing but names to say which: an
    ``async-start`` / ``-update`` / ``-done`` that is not printed as
    ``<opcode>-start``, and a ``kind=kCustom`` fusion, which is how the
    TPU compiler is remembered to emit a fused reduce-scatter (PERF.md §7:
    neither form has been seen in a trace of this repo). Those count where
    the instruction or the computation it ``calls=`` is named after a
    collective; the gathers and scatters that are ``kCustom`` fusions too
    are named ``fusion.N`` / ``fused_computation.N`` and stay compute."""
    parsed = _parse(text)
    if parsed is None:
        return False
    instr, _, opcode, rest = parsed
    if _COLLECTIVE.match(opcode):
        return True
    if not (_WRAPPER.match(opcode)
            or (opcode == "fusion" and "kind=kCustom" in rest)):
        return False
    called = re.search(r"calls=%?([\w.\-]+)", rest)
    return bool(_WRAPPED.match(instr)
                or (called and _WRAPPED.match(called.group(1))))


# ------------------------------------------------------- one device

def step_modules(modules) -> list:
    """The executions of the program that takes most of the device's time
    (the train step), in order. Other programs in the trace are ignored."""
    by_name = collections.Counter()
    for ev in modules:
        by_name[ev.name] += ev.end_ns - ev.start_ns
    if not by_name:
        return []
    top = by_name.most_common(1)[0][0]
    return sorted((ev for ev in modules if ev.name == top),
                  key=lambda e: e.start_ns)


def reduce_device(ops, async_ops, modules) -> dict:
    """One device's lines -> its numbers over the steady window.

    The window runs from the start of the first traced step program to
    the start of the last: whole periods of step and following gap, so
    the idle share does not depend on where the trace was cut. Needs at
    least two executions; returns None otherwise."""
    steps = step_modules(modules)
    if len(steps) < 2:
        return None
    lo, hi = steps[0].start_ns, steps[-1].start_ns
    segments = [(n, max(s, lo), min(e, hi)) for n, s, e in
                self_segments(ops) if min(e, hi) > max(s, lo)]
    busy = union((s, e) for _, s, e in segments)
    per_op = collections.Counter()
    for name, s, e in segments:
        per_op[name] += e - s
    calls = collections.Counter(
        ev.name for ev in ops if lo <= ev.start_ns < hi)
    # an instruction's text is parsed once, not once an event
    collectives = {name for name in per_op.keys()
                   | {ev.name for ev in async_ops} if is_collective(name)}
    compute = [(s, e) for n, s, e in segments if n not in collectives]
    collective = clip(
        [(s, e) for n, s, e in segments if n in collectives]
        + [(ev.start_ns, ev.end_ns) for ev in async_ops
           if ev.name in collectives], lo, hi)
    step_busy = [total(clip(busy, st.start_ns, st.end_ns))
                 for st in steps[:-1]]
    return {
        "window_ns": hi - lo,
        "busy_ns": total(busy),
        "steps": len(steps) - 1,
        "step_busy_ns": step_busy,
        "per_op_ns": dict(per_op),
        "per_op_calls": dict(calls),
        "gaps": subtract([(lo, hi)], busy),
        "collective_ns": total(collective),
        "collective_exposed_ns": total(subtract(collective, compute)),
    }


def attribute_gaps(gaps, host_spans, names) -> dict:
    """Idle nanoseconds by what the host was doing: each gap's overlap with
    the host spans called ``names`` (innermost wins where they nest), and
    ``other`` for the part no such span covers."""
    spans = [ev for ev in host_spans if ev.name in names]
    out = collections.Counter()
    covered = []
    for name, s, e in self_segments(spans):
        inside = total(o for g in gaps for o in clip([(s, e)], *g))
        if inside:
            out[name] += inside
        covered.append((s, e))
    out["other"] = total(subtract(gaps, covered))
    return {k: v for k, v in out.items() if v}


# ------------------------------------------------------- the whole trace

def reduce_trace(trace: dict, span_names) -> dict:
    """``trace``: {"devices": {plane: {"ops", "async", "modules"}},
    "host": [Event]} -> per-device numbers, their mean over the chips,
    the device operations by self time and the idle gaps by host span."""
    devices = {}
    for plane, lines in sorted(trace["devices"].items()):
        got = reduce_device(lines["ops"], lines["async"], lines["modules"])
        if got is not None:
            devices[plane] = got
    if not devices:
        return None
    n = len(devices)
    per_op, gaps = collections.Counter(), collections.Counter()
    calls = collections.Counter()
    for dev in devices.values():
        for name, ns in dev["per_op_ns"].items():
            per_op[name] += ns / n
            calls[name] += dev["per_op_calls"].get(name, 0) / n
        for name, ns in attribute_gaps(dev["gaps"], trace["host"],
                                       span_names).items():
            gaps[name] += ns / n
    return {
        "devices": devices,
        "chips": n,
        "busy_s": sum(d["busy_ns"] for d in devices.values()) / n / 1e9,
        "window_s": sum(d["window_ns"] for d in devices.values()) / n / 1e9,
        "steps": min(d["steps"] for d in devices.values()),
        "per_op_s": {k: v / 1e9 for k, v in per_op.items()},
        "per_op_calls": dict(calls),
        "idle_by_span_s": {k: v / 1e9 for k, v in gaps.items()},
    }


def breakdown(summary: dict, top: int = 10) -> dict:
    """The ten device operations that took most time and the idle time by
    host span, as the result line's ``breakdown`` wants them: seconds over
    the traced window, a chip's mean."""
    ops = sorted(summary["per_op_s"].items(), key=lambda kv: -kv[1])[:top]
    gaps = sorted(summary["idle_by_span_s"].items(),
                  key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[short_op_name(k), v] for k, v in ops],
            "idle_gaps": [[k, v] for k, v in gaps]}


def load_xplane(path: str, host_names=()) -> dict:
    """Read an ``.xplane.pb``. Keeps the three device lines the reduction
    uses and, of the host's events, those called ``host_names``."""
    from jax.profiler import ProfileData

    def events(line):
        return [Event(ev.name, int(ev.start_ns),
                      int(ev.start_ns + ev.duration_ns))
                for ev in line.events]

    wanted = {"XLA Ops": "ops", "Async XLA Ops": "async",
              "XLA Modules": "modules"}
    trace = {"devices": {}, "host": []}
    for plane in ProfileData.from_file(path).planes:
        if DEVICE_PLANE.match(plane.name):
            lines = {"ops": [], "async": [], "modules": []}
            for line in plane.lines:
                if line.name in wanted:
                    lines[wanted[line.name]] += events(line)
            trace["devices"][plane.name] = lines
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                trace["host"] += [ev for ev in events(line)
                                  if ev.name in host_names]
    return trace
