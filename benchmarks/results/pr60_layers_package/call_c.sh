#!/bin/bash
# one chip, the final tree: the two pairs call_b.sh's 3,600 s did not reach
# (every run of call B compiled cold)
python3 benchmarks/chip_cells.py pr60 others \
  qwen3next4l-b2s8k:pair:3100600361 phi4flash6l-b1s8k:pair:3100600371
