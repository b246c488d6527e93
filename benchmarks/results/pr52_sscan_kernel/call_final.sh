# the final tree: the change's bare step by scope, the benchmark's traced pair, one abba
python3 benchmarks/step_by_scope.py phi4flash6l-b1s8k 3 20260521
python3 benchmarks/chip_cells.py pr52_sscan_kernel traced_final phi4flash6l-b1s8k:ptraced:5200401 phi4flash6l-b1s8k:traced:5200401
python3 benchmarks/chip_cells.py pr52_sscan_kernel pairs_final phi4flash6l-b1s8k:abba:5200501
