# PR 57, call A (one chip): the cell on parent and change, three pairs
# untraced (parent, change, change, parent, then one more pair) and a traced
# pair on one seed.
set -x
python3 benchmarks/chip_cells.py pr57 first keyevl4l-b1s16k:abba:3100570101 keyevl4l-b1s16k:pair:3100570103 keyevl4l-b1s16k:ptraced:3100570111 keyevl4l-b1s16k:traced:3100570111
