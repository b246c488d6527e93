#!/bin/bash
# one chip: where the two-loop form's time goes (each kernel alone, a piece
# out at a time) and the substitution's column spread as a product with a
# block of ones (`variants_a.py`), blocks of 256 and 512 tokens
out=chiprun_out/pr64
export PROBE_VARIANTS=benchmarks/results/pr64_kda_two_loops/variants_a.py
python3 benchmarks/results/pr64_kda_two_loops/loop_probe.py $out/loop_probe_b.jsonl both 256 base no_inverse substitution_only merge_only per_chunk no_second no_first only_inverse ones_spread ones_spread_5 ones_spread_1
python3 benchmarks/results/pr64_kda_two_loops/loop_probe.py $out/loop_probe_b.jsonl both 512 base no_inverse only_inverse ones_spread ones_spread_5
