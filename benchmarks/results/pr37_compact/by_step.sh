set -x
R=/root/repo
mkdir -p $R/chiprun_out/pr37_compact/by_step
for side in change parent_bench; do
  cd $R/.bench_tree/$side
  rm -rf chiprun_out/step_counters
  python3 benchmarks/step_counters.py nemotronh9l-b1s8k 68 3700000231 3700000232 | cut -c1-300
  python3 benchmarks/step_counters.py smallthinker4l-b1s16k 38 3700000241 3700000242 | cut -c1-300
  mkdir -p $R/chiprun_out/pr37_compact/by_step/$side
  cp chiprun_out/step_counters/*.jsonl $R/chiprun_out/pr37_compact/by_step/$side/
done
cd $R
python3 benchmarks/results/pr37_compact/step_profile.py nemotronh9l-b1s8k 10 3700000002 | cut -c1-600
python3 benchmarks/results/pr37_compact/step_profile.py smallthinker4l-b1s16k 10 3700000001 | cut -c1-600
for f in none 2 2 none; do python3 benchmarks/results/pr37_compact/setup_probe.py nemotronh9l-b1s8k $f | cut -c1-500; done
for f in none 2 2 none; do python3 benchmarks/results/pr37_compact/setup_probe.py smallthinker4l-b1s16k $f | cut -c1-500; done
