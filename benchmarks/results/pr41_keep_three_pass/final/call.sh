set -x
python3 benchmarks/chip_cells.py pr41_keep_three_pass final nemotronh9l-b1s8k:traced:3100410301 olmoe1l-b2s4k:pair:3100410302 lfm2moe5l-b2s8k:pair:3100410303 smallthinker4l-b1s16k:pair:3100410304 gpt2m-b16-remat:pair:3100410305 gpt2s-b16:pair:3100410306
