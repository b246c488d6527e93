# PR 57, call B (one chip), the final tree (.bench_tree/change = `git archive
# $(git write-tree)`): the cell on parent and change, four pairs untraced
# (parent, change, change, parent twice over) and a traced pair on one seed.
set -x
python3 benchmarks/chip_cells.py pr57 final keyevl4l-b1s16k:abba:3100570201 keyevl4l-b1s16k:abba:3100570203 keyevl4l-b1s16k:ptraced:3100570211 keyevl4l-b1s16k:traced:3100570211
