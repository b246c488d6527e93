# PR 57, call C (one chip; run in ONE call with call_b.sh, after it): the bare step's 3-step profile by scope, phase
# and kernel, this tree's `benchmarks/step_by_scope.py` over each tree, and
# the two kernels alone against the parent's form.
set -x
root=$PWD
mkdir -p chiprun_out/pr57
for side in parent_bench change; do
  (cd .bench_tree/$side && python3 $root/benchmarks/step_by_scope.py keyevl4l-b1s16k 20 3100570301 | tail -n 1 > $root/chiprun_out/pr57/step_by_scope_$side.jsonl)
done
python3 benchmarks/results/pr57_indexer_kl/kernel_probe.py chiprun_out/pr57/kernel_probe_final.jsonl 3100570401 parent fused
