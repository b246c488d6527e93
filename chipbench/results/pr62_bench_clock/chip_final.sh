# PR 62's chip call on the final tree: `git add -A`, then
#   git archive $(git write-tree) | tar -x -C .bench_tree/change
#   chiprun --chips 1 --timeout 2400 -- bash chipbench/results/pr62_bench_clock/chip_final.sh
# the two cells whose readings PR 62 changes, traced and untraced, from the
# unpacked archive (not a git repository); lines to chiprun_out/pr62/.
set -u
root=$(pwd); out=$root/chiprun_out/pr62; mkdir -p $out
cd $root/.bench_tree/change
run() { # cell seed trace
  python3 -m chipbench.run --workload $1 --seed $2 --seconds 30 --trace $3 \
    > $out/final_$1_t$3.out 2> $out/final_$1_t$3.err
  echo "$1 trace=$3 seed=$2 rc=$?"; tail -n 1 $out/final_$1_t$3.out | cut -c1-3500; }
run smallthinker4l-b1s16k 3100620201 1
run smallthinker4l-b1s16k 3100620202 0
run olmoe1l-b2s4k 3100620203 1
run olmoe1l-b2s4k 3100620204 0
