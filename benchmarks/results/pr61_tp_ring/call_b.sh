#!/bin/bash
# four chips; the control: gpt2l-dp2tp2 (the same _tp_blocks at tp 2, exchange_sum's
# untouched branch; the parent's program by sha256), parent then change on one seed
python3 benchmarks/chip_cells.py pr61 control gpt2l-dp2tp2:pair:3100610201
