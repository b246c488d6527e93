"""Which instructions under a scope read or write a whole `[B, S, S]` array?
From the optimized HLO of a cell's step compiled for a DESCRIBED v5e (no
chip; `STEP_SCOPES_TEXT=<file>` of `benchmarks/results/pr38_scope/
step_scopes.py` writes the text):

    python3 benchmarks/results/pr57_indexer_kl/scope_arrays.py <hlo.txt> <scope> <S>

One line an instruction the device runs on its own (no fusion's body, no
reducer) whose `op_name` holds `<scope>` and whose result or operands (by
the results of the instructions it names) hold a `[B, S, S]` or `[S, S]`
array: its name, opcode, the result's such arrays, the operands' such
arrays, and the end of its `op_name`."""
import collections
import re
import sys

text, scope, S = open(sys.argv[1]).read(), sys.argv[2], sys.argv[3]
inner = set(re.findall(r"(?:calls|to_apply)=%?([\w.\-]+)", text))
whole = re.compile(r"\b([a-z]+\d+|pred)\[(?:\d+,)?%s,%s\]" % (S, S))
LINE = re.compile(r"^\s*(?:ROOT )?(%[\w.\-]+) = (.*?) ([\w\-]+)\((.*?)\)"
                  r"(?:, |$)")
result_of = {m.group(1): m.group(2)
             for m in map(LINE.match, text.splitlines()) if m}
inside, seen = None, collections.Counter()
for line in text.splitlines():
    head = re.match(r"^(?:ENTRY )?%?([\w.\-]+) \(.*\{\s*$", line)
    if head:
        inside = head.group(1)
        continue
    m, op_name = LINE.match(line), re.search(r'op_name="([^"]*)"', line)
    if inside in inner or not m or not op_name \
            or scope not in op_name.group(1):
        continue
    name, result, opcode, operands = m.groups()
    if opcode in ("get-tuple-element", "bitcast", "tuple"):
        continue
    wrote = whole.findall(result)
    read = [t for operand in re.findall(r"%[\w.\-]+", operands)
            for t in whole.findall(result_of.get(operand, ""))]
    if wrote or read:
        seen[re.sub(r"[.\d]+$", "", name)] += 1
        print(name, opcode, "writes", wrote, "reads", read,
              op_name.group(1)[-60:])
print("by name:", dict(seen))
