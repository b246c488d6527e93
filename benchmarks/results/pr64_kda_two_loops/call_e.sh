#!/bin/bash
# one chip, the tree as handed in (.bench_tree/change = git archive of the
# index — the FIRST form: two loops, one chunk a body of each;
# .bench_tree/parent_bench = the parent commit): the rule alone once
# more, the claimed cell traced on both sides, and the
# bare step's 3-step profile by scope, phase and kernel name of both trees
root=$PWD
out=chiprun_out/pr64
python3 benchmarks/results/pr59_kda_kernel/rule_probe.py $out/rule_probe_final.jsonl 256
python3 benchmarks/chip_cells.py pr64 final kimilinear5l-b2s8k:ptraced:3100640101 kimilinear5l-b2s8k:traced:3100640101
for side in parent_bench change; do
  (cd .bench_tree/$side && python3 $root/benchmarks/step_by_scope.py kimilinear5l-b2s8k 20 3100640201 | tail -n 1 > $root/$out/step_by_scope_$side.jsonl)
done
