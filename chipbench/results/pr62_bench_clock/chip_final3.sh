# PR 62, second session, after the last edit (`compared` out of
# `compare.compare`'s result): the same two cells once more from
# `git archive $(git write-tree)` unpacked into .bench_tree/change.
#   chiprun --chips 1 --timeout 1800 -- bash chipbench/results/pr62_bench_clock/chip_final3.sh
set -u
root=$(pwd); out=$root/chiprun_out/pr62; mkdir -p $out
cd $root/.bench_tree/change
run() { # cell seed trace
  python3 -m chipbench.run --workload $1 --seed $2 --seconds 30 --trace $3 \
    > $out/final3_$1_$2_t$3.out 2> $out/final3_$1_$2_t$3.err
  echo "$1 trace=$3 seed=$2 rc=$?"
  tail -n 1 $out/final3_$1_$2_t$3.out | python3 -c "import json,sys; d=json.load(sys.stdin); print(d['correct'], list(d)[-1], dict(list(d['compared'].items())[:5]), {k: v['value'] for k, v in d['metrics'].items() if k in ('tokens_per_s_per_chip','mfu','setup_s','step_ms_p90','train_step.device_ms')})"
  tail -n 2 $out/final3_$1_$2_t$3.err; }
run phi4flash6l-b1s8k 3100620504 1
run phi4flash6l-b1s8k 3100620505 0
run gpt2s-b16 3100620513 0
