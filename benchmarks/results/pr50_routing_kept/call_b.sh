# one call of the chip tool: the claimed cell through the benchmark's own
# command — pairs (parent, change, change, parent on two seeds a plan). A
# run of this cell takes 400–550 s (its float32 reference among it), so the
# call's limit held three pairs and the change of a fourth
python3 benchmarks/chip_cells.py pr50_routing_kept pairs qwen3next4l-b2s8k:abba:5000101 qwen3next4l-b2s8k:abba:5000103 qwen3next4l-b2s8k:abba:5000105
