# one call of the chip tool: the bare step by scope and phase, change then
# parent (each tree's own benchmarks/step_by_scope.py, from its own root)
for side in change parent_bench; do
  (cd .bench_tree/$side && python3 benchmarks/step_by_scope.py qwen3next4l-b2s8k 6 5000401 2>&1 | grep '^{' | cut -c1-300)
  mkdir -p chiprun_out/pr50_routing_kept/step_by_scope
  cp .bench_tree/$side/chiprun_out/step_by_scope/step_by_scope.jsonl chiprun_out/pr50_routing_kept/step_by_scope/$side.jsonl
done
