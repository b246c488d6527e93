#!/bin/bash
# one chip, the first call, before anything else is written: the rule alone as
# PR 59 shipped it, then PR 59's unmeasured `kda_two_loops.py` (a block's
# chunks in two loops), blocks 256 and 512 each; then where the shipped form's
# time goes (`ablate.py`: its base, the inverse out, the diagonal terms out)
out=chiprun_out/pr64
python3 benchmarks/results/pr59_kda_kernel/rule_probe.py $out/rule_probe_shipped.jsonl 256 512
PROBE_MODULE=benchmarks/results/pr59_kda_kernel/kda_two_loops.py python3 benchmarks/results/pr59_kda_kernel/rule_probe.py $out/rule_probe_two_loops.jsonl 256 512
python3 benchmarks/results/pr59_kda_kernel/ablate.py $out/ablate_shipped.jsonl base no_inverse no_band
