# one call of the chip tool: the claimed cell's traced pair, then a pair
# (parent, change on one seed) of two cells that run the changed code
python3 benchmarks/chip_cells.py pr50_routing_kept traced qwen3next4l-b2s8k:ptraced:5000201 qwen3next4l-b2s8k:traced:5000201
python3 benchmarks/chip_cells.py pr50_routing_kept others joyaiflash5l-b2s8k:pair:5000301 lfm2moe5l-b2s8k:pair:5000341
