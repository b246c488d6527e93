# one call of the chip tool: the change alone again on seed 5000108, whose
# run in call E held one step of 3.39 s waiting for the loss (step 10)
python3 benchmarks/chip_cells.py pr50_routing_kept confirm2 qwen3next4l-b2s8k:runs1:5000108
