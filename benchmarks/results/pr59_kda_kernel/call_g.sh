#!/bin/bash
# one chip: the rule alone as shipped, then with a block's chunks in two
# loops (`kda_two_loops.py`, not shipped); then the tree as handed in
# (.bench_tree/change): two more timed runs on seeds of their own and the
# rounded-carry control
python3 benchmarks/results/pr59_kda_kernel/rule_probe.py chiprun_out/pr59/rule_probe6.jsonl 256
PROBE_MODULE=benchmarks/results/pr59_kda_kernel/kda_two_loops.py python3 benchmarks/results/pr59_kda_kernel/rule_probe.py chiprun_out/pr59/rule_probe6_two_loops.jsonl 256 512
python3 benchmarks/chip_cells.py pr59 final kimilinear5l-b2s8k:runs2:3100590321 kimilinear5l-b2s8k:below-bf16_state:3100590331
