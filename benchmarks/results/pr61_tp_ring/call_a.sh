#!/bin/bash
# four chips; .bench_tree/change = git archive of the index (the final tree's
# committed files), .bench_tree/parent_bench = git archive f367a17 (the
# benchmark's files are the same on both sides): the claimed cell traced on both
# sides, then parent, change, change, parent untraced on two seeds, then the
# bare step's profile in the parent's form, the tree's, and the tree's without
# its optimization barrier (the exchanges' waits by loop body and sub-layer)
python3 benchmarks/chip_cells.py pr61 final \
  gpt2l-tp4:ptraced:3100610101 gpt2l-tp4:traced:3100610101 \
  gpt2l-tp4:abba:3100610111
python3 benchmarks/results/pr61_tp_ring/forms_probe.py 8 6100102 whole two_way two_way_copied
