#!/bin/bash
# one chip, the module as it ships (two loops, a column's spread a product
# with a block of ones): the rule alone, parent (PR 59's one-loop module, out
# of .bench_tree/parent) then change, blocks 256 and 512; where the new
# form's time goes (`ablate.py`: the whole rule; `loop_probe.py`: each kernel
# alone with a loop or the inverses left out)
out=chiprun_out/pr64
PROBE_MODULE=.bench_tree/parent/ray_tpu/ops/kda.py python3 benchmarks/results/pr59_kda_kernel/rule_probe.py $out/rule_probe_parent.jsonl 256 512
python3 benchmarks/results/pr59_kda_kernel/rule_probe.py $out/rule_probe_change.jsonl 256 512
python3 benchmarks/results/pr59_kda_kernel/ablate.py $out/ablate_change.jsonl base no_inverse no_band
PROBE_VARIANTS=benchmarks/results/pr64_kda_two_loops/variants_b.py python3 benchmarks/results/pr64_kda_two_loops/loop_probe.py $out/loop_probe_d.jsonl both 256 base no_inverse per_chunk no_second no_first dma_only
