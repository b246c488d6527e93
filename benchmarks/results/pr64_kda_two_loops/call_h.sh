#!/bin/bash
# one chip: the second loop's bodies in one — the forward's (4 chunks; 2 and 8
# at blocks of 128 and 512), the backward's whole or two chunks a body
out=chiprun_out/pr64
export PROBE_VARIANTS=benchmarks/results/pr64_kda_two_loops/variants_c.py
python3 benchmarks/results/pr64_kda_two_loops/loop_probe.py $out/loop_probe_h.jsonl both 256 base second_unrolled second_unrolled_both second_unrolled_bwd_2
python3 benchmarks/results/pr64_kda_two_loops/loop_probe.py $out/loop_probe_h.jsonl fwd 512 base second_unrolled
python3 benchmarks/results/pr64_kda_two_loops/loop_probe.py $out/loop_probe_h.jsonl fwd 128 base second_unrolled
