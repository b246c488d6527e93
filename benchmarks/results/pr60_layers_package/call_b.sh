#!/bin/bash
# one chip, the final tree: one untraced pair (parent, change, one seed) of one
# cell of every other one-chip configuration — each runs the moved code
python3 benchmarks/chip_cells.py pr60 others \
  gpt2s-b16:pair:3100600301 olmoe1l-b2s4k:pair:3100600311 \
  nemotronh9l-b1s8k:pair:3100600321 smallthinker4l-b1s16k:pair:3100600331 \
  lfm2moe5l-b2s8k:pair:3100600341 joyaiflash5l-b2s8k:pair:3100600351 \
  qwen3next4l-b2s8k:pair:3100600361 phi4flash6l-b1s8k:pair:3100600371
