set -x
T=$PWD
O=$T/chiprun_out/pr41_keep_three_pass
mkdir -p $O
echo "cache dir: ${JAX_COMPILATION_CACHE_DIR:-unset}"
python3 benchmarks/chip_cells.py pr41_keep_three_pass first nemotronh9l-b1s8k:ptraced:3100410101 nemotronh9l-b1s8k:traced:3100410101
for side in parent_bench change; do
  (cd .bench_tree/$side && python3 benchmarks/step_counters.py nemotronh9l-b1s8k 90 3100410101 3100410102 > $O/counters_$side.jsonl 2> $O/counters_$side.err; echo "counters $side rc=$?")
  (cd .bench_tree/$side && python3 benchmarks/results/pr38_scope/step_by_scope.py nemotronh9l-b1s8k 20 3100410101 > $O/by_scope_$side.jsonl 2> $O/by_scope_$side.err; echo "by_scope $side rc=$?")
done
tail -c 600 $O/counters_*.err $O/by_scope_*.err
