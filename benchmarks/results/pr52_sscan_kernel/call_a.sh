# the change's bare step by scope, then the benchmark's traced pair
python3 benchmarks/step_by_scope.py phi4flash6l-b1s8k 3 20260521
python3 benchmarks/chip_cells.py pr52_sscan_kernel traced phi4flash6l-b1s8k:ptraced:5200101 phi4flash6l-b1s8k:traced:5200101
