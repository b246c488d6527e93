# one call of the chip tool: the claimed cell again on the tree AS COMMITTED
# (.bench_tree/change = git archive $(git write-tree) after the last edit:
# comments and tests moved, the compiled step did not — same_program.txt),
# two more pairs on two new seeds
python3 benchmarks/chip_cells.py pr50_routing_kept confirm qwen3next4l-b2s8k:abba:5000107
