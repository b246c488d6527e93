# PR 62, second session, the final tree: `git add -A`, then
#   rm -rf .bench_tree/change; mkdir -p .bench_tree/change
#   git archive $(git write-tree) | tar -x -C .bench_tree/change
#   chiprun --chips 1 --timeout 3400 -- bash chipbench/results/pr62_bench_clock/chip_final2.sh
# `phi4flash6l-b1s8k` on fresh seeds and on the driver's in the other trace
# mode, and `gpt2s-b16` for the line's new last key in a cell whose
# comparison nothing else of this session touches; from the unpacked archive
# (not a git repository); lines to chiprun_out/pr62/.
set -u
root=$(pwd); out=$root/chiprun_out/pr62; mkdir -p $out
cd $root/.bench_tree/change
run() { # cell seed trace
  python3 -m chipbench.run --workload $1 --seed $2 --seconds 30 --trace $3 \
    > $out/final2_$1_$2_t$3.out 2> $out/final2_$1_$2_t$3.err
  echo "$1 trace=$3 seed=$2 rc=$?"; tail -n 1 $out/final2_$1_$2_t$3.out | cut -c1-300
  tail -n 1 $out/final2_$1_$2_t$3.out | python3 -c "import json,sys; d=json.load(sys.stdin); print(list(d)[-1], dict(list(d['compared'].items())[:5]), {k: v['value'] for k, v in d['metrics'].items() if k in ('tokens_per_s_per_chip','mfu','setup_s','step_ms_p90')})"
  tail -n 2 $out/final2_$1_$2_t$3.err; }
run phi4flash6l-b1s8k 3100620501 0
run phi4flash6l-b1s8k 3100620502 0
run phi4flash6l-b1s8k 3100620503 1
run phi4flash6l-b1s8k 314767261 1
run phi4flash6l-b1s8k 2124027345 0
run gpt2s-b16 3100620511 0
run gpt2s-b16 3100620512 1
