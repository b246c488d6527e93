#!/bin/bash
# one chip, the final trees: parent, change, change, parent on two seeds
python3 benchmarks/chip_cells.py pr64 final kimilinear5l-b2s8k:abba:3100640311
