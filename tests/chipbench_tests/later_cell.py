"""``BENCHMARK.json`` as the next ``model_config`` PR will leave it: a copy
with a made-up configuration, a made-up cell that remats and that cell's
own recomputation metric appended, each behind what is there. The tests
that look their entries up in the manifest run on it too, so that the next
cell breaks none of them (ten cases failed on every tree from PR 40 to
PR 61 because they took the manifest's last entries for their own)."""
import copy

CELL = "madeup2l-b16-remat"
# files that exist: a tiny configuration of this directory under a remat
# traffic mix of the benchmark's, a pair no cell has; the metric's file is
# `metrics/train_step.madeup_recompute_share.json` beside this module
CONFIG, TRAFFIC = "gpt2-tiny", "fit-b16-remat"
METRIC = "train_step.madeup_recompute_share"


def with_a_later_cell(manifest: dict) -> dict:
    grown = copy.deepcopy(manifest)
    grown["configs"].append({
        "name": CONFIG, "source": "https://example.org/made-up",
        "file": f"tests/chipbench_tests/configs/{CONFIG}.json",
        "reduced": [],
        "why": "made up by tests/chipbench_tests/later_cell.py"})
    grown["workloads"].append({
        "name": CELL, "config": CONFIG, "traffic": TRAFFIC, "chips": 1,
        "why": "made up by tests/chipbench_tests/later_cell.py"})
    grown["per_layer"].append({
        "name": METRIC, "unit": "%", "better": "lower",
        "source": "device_trace", "layer": "train_step", "moves": "mfu",
        "workloads": [CELL]})
    return grown
