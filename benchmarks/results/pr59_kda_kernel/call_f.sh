#!/bin/bash
# one chip, the tree as handed in (.bench_tree/change = git archive of the
# index): the claimed cell traced on both sides, then parent, change, change,
# parent on two seeds
python3 benchmarks/chip_cells.py pr59 final kimilinear5l-b2s8k:ptraced:3100590301 kimilinear5l-b2s8k:traced:3100590301 kimilinear5l-b2s8k:abba:3100590311
