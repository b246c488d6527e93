for k in 2 4 8; do PROBE_TOKENS_A_BODY=$k python3 benchmarks/results/pr52_sscan_kernel/kernel_probe.py 64 chiprun_out/pr52/kernel_probe6.jsonl 2>&1 | grep '"kernel"'; done
