"""A cell's own train step in a bare loop, ONE process on the chip, for what
the benchmark's result line has no place for: EVERY scalar the step's
`metrics` hold, by step (the harness fetches the loss alone), beside each
step's time.

    python3 benchmarks/step_counters.py <cell> <steps> [<field>=<number> ...] <seed> [<seed> ...]

from the repo root, through the chip tool. State and tokens are those of a
benchmark run of that `--seed` (`chipbench/jobs/train_fit.py`'s key and
rows; a `<field>=<number>` sets that field of the preset instead, for a
what-if on the same program). Prints one JSON line a seed — `step_ms` and each counter as lists by
step, the median step of the second half beside the counters' means over it
— and appends it to chiprun_out/step_counters/<cell>.jsonl. For
`smallthinker4l-b1s16k` the counter is `moe_held`, the assignments that
reached an expert held here: what the cell's throughput follows (PERF.md §6,
PR 36) and what `mfu` credits at its expectation instead; beside it
`moe_compact`, the routed layers that ran on the share's bounded prefix of
the assignments that step (its mean ÷ the routed layers is the hit share)."""
import dataclasses
import importlib
import json
import math
import os
import statistics
import sys
import time

ROOT = os.getcwd()
sys.path.insert(0, ROOT)

import jax  # noqa: E402

from chipbench import catalog, flops, generate  # noqa: E402
from ray_tpu.parallel.mesh import MeshConfig, create_mesh  # noqa: E402
from ray_tpu.parallel.train_step import (  # noqa: E402
    default_optimizer,
    make_train_state,
    make_train_step,
)


def main(cell_name, steps, seeds, overrides=()):
    cell = catalog.resolve_cell(catalog.load_manifest(), cell_name,
                                "end_to_end")
    traffic = cell["traffic"]
    module_name, preset = cell["model"]["entry"].split(":")
    module = importlib.import_module(module_name)
    cfg = dataclasses.replace(getattr(module, preset)(),
                              attention=traffic["attention"],
                              remat=traffic["remat"], **dict(overrides))
    devices = jax.local_devices()[:math.prod(traffic["mesh"].values())]
    mesh = create_mesh(MeshConfig(**traffic["mesh"]), devices=devices)
    opt = default_optimizer(**traffic["optimizer"])
    step = make_train_step(lambda p, b: module.loss_fn(p, b, cfg, mesh),
                           opt, mesh)
    out = os.path.join(ROOT, "chiprun_out", "step_counters")
    os.makedirs(out, exist_ok=True)
    for seed in seeds:
        rows = generate.token_rows(
            traffic, flops.padded_vocab(cell["model"]["vocab_size"]), seed)
        state = make_train_state(lambda rng: module.init(rng, cfg),
                                 jax.random.PRNGKey(seed), opt, mesh,
                                 module.partition_specs(cfg))
        batch = traffic["batch"]
        record = {"cell": cell_name, "seed": seed, **dict(overrides),
                  "device": devices[0].device_kind, "step_ms": []}
        for i in range(steps):
            at = (i * batch) % (len(rows) - batch + 1)
            t0 = time.perf_counter()
            state, metrics = step(state, {"tokens": rows[at:at + batch]})
            metrics = {k: float(v) for k, v in metrics.items()
                       if getattr(v, "ndim", 0) == 0}
            record["step_ms"].append(1e3 * (time.perf_counter() - t0))
            for k, v in metrics.items():
                record.setdefault(k, []).append(v)
        half = steps // 2
        record["second_half"] = {
            k: statistics.median(v[half:]) if k == "step_ms"
            else statistics.fmean(v[half:])
            for k, v in record.items() if isinstance(v, list)}
        del state
        line = json.dumps(record)
        print(line, flush=True)
        with open(os.path.join(out, cell_name + ".jsonl"), "a") as f:
            f.write(line + "\n")


if __name__ == "__main__":
    sets = [a.split("=") for a in sys.argv[3:] if "=" in a]
    main(sys.argv[1], int(sys.argv[2]),
         [int(a) for a in sys.argv[3:] if "=" not in a],
         [(k, float(v)) for k, v in sets])
