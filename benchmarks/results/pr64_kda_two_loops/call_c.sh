#!/bin/bash
# one chip: the first loop two and four chunks a body (its bodies read no
# state), a column's spread a product of its own, both, the spread in one
# six-pass product, and a grid step with no work at all (`variants_b.py`)
out=chiprun_out/pr64
export PROBE_VARIANTS=benchmarks/results/pr64_kda_two_loops/variants_b.py
python3 benchmarks/results/pr64_kda_two_loops/loop_probe.py $out/loop_probe_c.jsonl both 256 base first_unroll_2 first_unroll_4 ones_1 ones_1_unroll_2 ones_exact_1 dma_only
python3 benchmarks/results/pr64_kda_two_loops/loop_probe.py $out/loop_probe_c.jsonl both 128 base ones_1 dma_only
