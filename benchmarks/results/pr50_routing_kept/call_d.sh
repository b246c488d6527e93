# one call of the chip tool: the two other share cells, and the routing by
# layer on the claimed cell's faster step (90 steps, three seeds)
python3 benchmarks/chip_cells.py pr50_routing_kept others nemotronh9l-b1s8k:abba:5000311 smallthinker4l-b1s16k:pair:5000351
python3 benchmarks/held_by_layer.py qwen3next4l-b2s8k 90 5000501 5000502 5000503 2>&1 | grep '^{' | cut -c1-300
