#!/bin/bash
# one chip; .bench_tree/change = git archive of the index, .bench_tree/parent_bench
# = git archive 6278d36 (the benchmark's files are the same on both sides): the two
# cells with the most code in the moved seams, traced on both sides, then one
# untraced pair each
python3 benchmarks/chip_cells.py pr60 final \
  kimilinear5l-b2s8k:ptraced:3100600101 kimilinear5l-b2s8k:traced:3100600101 \
  keyevl4l-b1s16k:ptraced:3100600201 keyevl4l-b1s16k:traced:3100600201 \
  kimilinear5l-b2s8k:pair:3100600111 keyevl4l-b1s16k:pair:3100600211
