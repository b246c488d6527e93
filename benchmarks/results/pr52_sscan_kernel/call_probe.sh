set -x
python3 benchmarks/results/pr52_sscan_kernel/kernel_probe.py 32,64,128 chiprun_out/pr52/kernel_probe2.jsonl
python3 benchmarks/results/pr52_sscan_kernel/variants_probe.py chiprun_out/pr52/variants_fwd2.jsonl
