# one call of the chip tool: warm `setup_s` by phase, parent and change in turn
S=benchmarks/results/pr46_mixer_stages
seed=4900700
for side in parent_bench change change parent_bench parent_bench change; do
  python3 $S/setup_split.py .bench_tree/$side qwen3next4l-b2s8k $seed ${side}_$seed 2>&1 | grep "^{" | cut -c1-500
  seed=$((seed+1))
done
python3 benchmarks/results/pr49_delta_kernel/kernel_probe.py 512x4,256x4,1024x4 chiprun_out/pr49/kernel_probe_final.jsonl 2>&1 | grep what | cut -c1-400
