#!/bin/bash
# The proof of "the same program", no chip: every cell's train step compiled
# for a DESCRIBED v5e on two trees (pr34's step_program.py, attention forced to
# "flash" as tests/chipbench_tests/test_zz_chipbench_compile.py forces it) and
# `benchmarks/step_hlo_compare.py` on each pair of dumps.
#   same_program.sh <parent tree> <change tree> <scratch dir>  > same_program.txt
# A tree is a checkout (`git archive <commit> | tar -x -C <dir>`). ~12 minutes
# a tree on 8 cores, one process at a time. A compile is not a chip run.
set -u
parent=$1; change=$2; scratch=$3
here=$(cd "$(dirname "$0")/../../.." && pwd)
cells=$(python3 -c "import json; print(' '.join(w['name'] for w in json.load(open('$here/BENCHMARK.json'))['workloads']))")
for side in parent change; do
  tree=$(eval echo \$$side)
  for c in $cells; do
    d=$scratch/$side/$c
    [ -n "$(ls $d/*after_optimizations.txt 2>/dev/null)" ] && continue
    mkdir -p $d
    JAX_PLATFORMS=cpu TPU_LOG_DIR=disabled python3 \
      $here/benchmarks/results/pr34_nemotron_h/step_program.py $tree $c $d >&2
  done
done
same=0; n=0
printf "%-24s %-64s %-64s %s\n" cell "parent sha256 (stripped HLO of jit_step)" "change sha256" verdict
for c in $cells; do
  out=$(JAX_PLATFORMS=cpu python3 $here/benchmarks/step_hlo_compare.py $scratch/parent/$c $scratch/change/$c 2>/dev/null)
  a=$(echo "$out" | sed -n 1p | cut -d' ' -f1); b=$(echo "$out" | sed -n 2p | cut -d' ' -f1)
  v=$(echo "$out" | tail -1)
  printf "%-24s %-64s %-64s %s\n" $c $a $b "$v"
  n=$((n+1)); [ "$v" = "SAME PROGRAM" ] && same=$((same+1))
done
echo "$same of $n cells: SAME PROGRAM"
[ $same -eq $n ]
