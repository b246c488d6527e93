#!/bin/bash
# one chip: the rule alone — the norms' sums back on the XLU, one roll back an
# offset in the band's pullback
python3 benchmarks/results/pr59_kda_kernel/rule_probe.py chiprun_out/pr59/rule_probe5.jsonl 256x1 128x1 512x1
