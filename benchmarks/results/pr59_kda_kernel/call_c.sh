#!/bin/bash
# one chip: the rule alone as the tree stands, then with one piece of a
# chunk's arithmetic taken out at a time (timing only)
python3 benchmarks/results/pr59_kda_kernel/rule_probe.py chiprun_out/pr59/rule_probe3.jsonl 256x1x8 256x2x8
python3 benchmarks/results/pr59_kda_kernel/ablate.py chiprun_out/pr59/ablate.jsonl
