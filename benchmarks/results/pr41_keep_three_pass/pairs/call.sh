set -x
python3 benchmarks/chip_cells.py pr41_keep_three_pass pairs nemotronh9l-b1s8k:abba:3100410201 nemotronh9l-b1s8k:abba:3100410203 nemotronh9l-b1s8k:abba:3100410205
