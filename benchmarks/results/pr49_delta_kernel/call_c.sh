# one call of the chip tool: the bare step by scope, the routing by layer for
# 90 steps on three seeds, the e4m3 control through the benchmark's command
python3 benchmarks/step_by_scope.py qwen3next4l-b2s8k 6 4900401 2>&1 | grep '^{' | cut -c1-400
python3 benchmarks/held_by_layer.py qwen3next4l-b2s8k 90 4900501 4900502 4900503 2>&1 | grep '^{' | cut -c1-300
python3 benchmarks/chip_cells.py pr49_delta_kernel control qwen3next4l-b2s8k:below-e4m3:4900601
