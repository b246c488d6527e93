#!/bin/bash
# one chip: the rule alone, the kernels' constants swept (block x unrolled x
# slab; slab 128 is the whole-array order of the first form), then the cell's
# bare step folded by scope, phase and kernel name
python3 benchmarks/results/pr59_kda_kernel/rule_probe.py chiprun_out/pr59/rule_probe2.jsonl 256x1x128 256x1x8 256x2x8 512x4x8 256x1x16
python3 benchmarks/step_by_scope.py kimilinear5l-b2s8k 12 3100590201
