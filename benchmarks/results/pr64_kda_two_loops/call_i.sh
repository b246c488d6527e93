#!/bin/bash
# one chip, the FINAL tree as handed in (.bench_tree/change = git archive of
# the index; .bench_tree/parent_bench = the parent commit): the claimed cell
# traced on both sides; the bare step's 3-step profile by scope, phase and
# kernel name of both trees; the rule alone, parent's module then the
# change's; what is left of the inverses and the diagonal terms in the final
# form (`ablate.py`), each kernel alone (`loop_probe.py`)
root=$PWD
out=chiprun_out/pr64
python3 benchmarks/chip_cells.py pr64 final kimilinear5l-b2s8k:ptraced:3100640301 kimilinear5l-b2s8k:traced:3100640301
for side in parent_bench change; do
  (cd .bench_tree/$side && python3 $root/benchmarks/step_by_scope.py kimilinear5l-b2s8k 20 3100640401 | tail -n 1 > $root/$out/final/step_by_scope_$side.jsonl)
done
PROBE_MODULE=.bench_tree/parent/ray_tpu/ops/kda.py python3 benchmarks/results/pr59_kda_kernel/rule_probe.py $out/final/rule_probe_parent.jsonl 256 512
python3 benchmarks/results/pr59_kda_kernel/rule_probe.py $out/final/rule_probe_change.jsonl 256 512
python3 benchmarks/results/pr59_kda_kernel/ablate.py $out/final/ablate_change.jsonl base no_inverse no_band
python3 benchmarks/results/pr64_kda_two_loops/loop_probe.py $out/final/loop_probe_final.jsonl both 256 base
