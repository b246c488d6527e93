#!/bin/bash
# one chip: the forward kernel's second loop other ways (`variants_c.py`)
out=chiprun_out/pr64
export PROBE_VARIANTS=benchmarks/results/pr64_kda_two_loops/variants_c.py
python3 benchmarks/results/pr64_kda_two_loops/loop_probe.py $out/loop_probe_g.jsonl fwd 256 base second_unrolled uw_between uw_between_unrolled
