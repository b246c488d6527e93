#!/bin/bash
# one chip: the rule alone — the lane sums on the MXU, a loop body in stages —
# at several (block x unrolled), then the ablations again
python3 benchmarks/results/pr59_kda_kernel/rule_probe.py chiprun_out/pr59/rule_probe4.jsonl 256x1 256x2 256x4 512x4 512x2
python3 benchmarks/results/pr59_kda_kernel/ablate.py chiprun_out/pr59/ablate2.jsonl base no_band no_inverse
