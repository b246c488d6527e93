"""What a compiled step's layer loops hold of the `tp` exchanges (no chip).

    python3 benchmarks/results/pr61_tp_ring/loop_bodies.py <compiled text> ...

`<compiled text>` is a whole train step compiled for a DESCRIBED v5e:2x2, as
`STEP_SCOPES_TEXT=<file> benchmarks/results/pr38_scope/step_scopes.py <tree>
<cell> <out.json>` leaves it: the scheduled HLO, so the order of a
computation's lines is the order the chip runs them in. For every computation
that holds a `collective-permute-start`: the pairs by shape and direction,
the blocking collectives beside them, and for each start what is scheduled
before its done — matmuls (`convolution`), Mosaic kernels, fusions — and how
many other exchanges are in flight with it; then the body's schedule in one
line (S<n> / D<n>: the n-th exchange's start and done, M: a matmul fusion, K:
a Mosaic kernel, f: another fusion, C: a copy). A compile is not a chip run:
no time here is the program's."""
import collections
import re
import sys

LINE = re.compile(r"^\s*(?:ROOT )?%([\w.\-]+) = (.*?) ([\w\-]+)\((.*)$")
BLOCKING = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
            "collective-permute")


def computations(text):
    name, lines = None, []
    for line in text.splitlines():
        head = re.match(r"^(?:ENTRY )?%([\w.\-]+) \(.*\{\s*$", line)
        if head:
            name, lines = head.group(1), []
        elif line.startswith("}") and name:
            yield name, lines
            name = None
        elif name:
            m = LINE.match(line)
            if m:
                lines.append(m.groups())


def kind(op, rest):
    if op == "fusion":
        if "convolution" in rest or "kind=kOutput" in rest:
            return "matmul fusion"
        return "fusion"
    if op == "custom-call":
        return "kernel" if "tpu_custom_call" in rest else "custom-call"
    return op


def report(path):
    text = open(path).read()
    print(f"== {path}")
    for name, lines in computations(text):
        starts = {n: i for i, (n, _, op, _) in enumerate(lines)
                  if op == "collective-permute-start"}
        if not starts:
            continue
        dones = {}
        for i, (n, _, op, rest) in enumerate(lines):
            if op == "collective-permute-done":
                dones[re.match(r"%([\w.\-]+)", rest).group(1)] = i
        shapes = collections.Counter()
        for n, i in starts.items():
            shape = re.match(r"\((\w+\[[\d,]*\])", lines[i][1]).group(1)
            pairs = re.findall(r"\{(\d+),(\d+)\}", re.search(
                r"source_target_pairs=\{([\d,{}]*)\}", lines[i][3]).group(1))
            source, target = map(int, pairs[0])
            way = "to rank+1" if (target - source) % len(pairs) == 1 \
                else "to rank-1"
            shapes[(shape, way)] += 1
        blocking = collections.Counter(op for _, _, op, _ in lines
                                       if op in BLOCKING)
        print(f"-- {name}: {len(lines)} instructions, {len(starts)} "
              f"collective-permute-start / {len(dones)} -done pairs; "
              f"blocking collectives: {dict(blocking) or 'none'}")
        for (shape, way), count in sorted(shapes.items()):
            print(f"   {count:3d} x {shape} {way}")
        between = collections.Counter()
        empty, flight = 0, []
        for n, i in starts.items():
            inside = [kind(op, rest) for _, _, op, rest in lines[i + 1:dones[n]]]
            work = [k for k in inside if k in ("matmul fusion", "kernel",
                                               "fusion", "convolution")]
            between.update(work)
            empty += not work
            flight.append(sum(1 for m, j in starts.items()
                              if m != n and j < dones[n] and dones[m] > i))
        order = {n: k for k, n in enumerate(starts)}
        marks = {"matmul fusion": "M", "kernel": "K", "fusion": "f",
                 "copy": "C"}
        line = []
        for n, _, op, rest in lines:
            if op == "collective-permute-start":
                line.append(f"S{order[n]}")
            elif op == "collective-permute-done":
                line.append(
                    f"D{order[re.match(r'%([\w.\-]+)', rest).group(1)]}")
            elif kind(op, rest) in marks:
                line.append(marks[kind(op, rest)])
        print(f"   between a start and its done, all pairs together: "
              f"{dict(between)}; pairs with no compute between: {empty}; "
              f"other exchanges in flight with one: min {min(flight)}, "
              f"max {max(flight)}")
        print("   schedule: " + " ".join(line))


for path in sys.argv[1:]:
    report(path)
