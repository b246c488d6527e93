"""What a share's bounds cost BEFORE the first step (the benchmark's
`setup_s`): the cell's own step traced and lowered, compiled or loaded from
the compile cache, and run twice — one process, the times of each phase:

    python3 benchmarks/results/pr37_compact/setup_probe.py <cell> <factors>

`<factors>`: `layers._BOUND_FACTORS` for this process, e.g. `2,4,8`, `2`,
or `none` (no bound: the parent's program). Run a variant twice to see it
cold and warm. Prints one JSON line and appends it to
chiprun_out/pr37_compact/setup_probe.jsonl."""
import dataclasses
import importlib
import json
import math
import os
import sys
import time

T0 = time.time()
ROOT = os.getcwd()
sys.path.insert(0, ROOT)

import jax  # noqa: E402

from chipbench import catalog, flops, generate  # noqa: E402
from ray_tpu.models import layers  # noqa: E402
from ray_tpu.parallel.compile_watch import configure_compile_cache  # noqa: E402
from ray_tpu.parallel.mesh import MeshConfig, create_mesh  # noqa: E402
from ray_tpu.parallel.train_step import (  # noqa: E402
    default_optimizer,
    make_train_state,
    make_train_step,
)


def main(cell_name, factors):
    layers._BOUND_FACTORS = tuple(
        int(f) for f in factors.split(",") if f != "none")
    configure_compile_cache()
    cache = {"hits": 0, "misses": 0}

    def on_event(event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            cache["hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            cache["misses"] += 1

    jax.monitoring.register_event_listener(on_event)
    cell = catalog.resolve_cell(catalog.load_manifest(), cell_name,
                                "end_to_end")
    traffic = cell["traffic"]
    module_name, preset = cell["model"]["entry"].split(":")
    module = importlib.import_module(module_name)
    cfg = dataclasses.replace(getattr(module, preset)(),
                              attention=traffic["attention"],
                              remat=traffic["remat"])
    devices = jax.local_devices()[:math.prod(traffic["mesh"].values())]
    mesh = create_mesh(MeshConfig(**traffic["mesh"]), devices=devices)
    opt = default_optimizer(**traffic["optimizer"])
    marks = {"jax_up": time.time() - T0}
    t = time.time()
    state = make_train_state(lambda rng: module.init(rng, cfg),
                             jax.random.PRNGKey(7), opt, mesh,
                             module.partition_specs(cfg))
    jax.block_until_ready(state)
    marks["state"] = time.time() - t
    step = make_train_step(lambda p, b: module.loss_fn(p, b, cfg, mesh),
                           opt, mesh)
    rows = generate.token_rows(
        traffic, flops.padded_vocab(cell["model"]["vocab_size"]), 7)
    batch = {"tokens": rows[:traffic["batch"]]}
    t = time.time()
    lowered = step.lower(state, batch)
    marks["trace_and_lower"] = time.time() - t
    t = time.time()
    before = dict(cache)
    lowered.compile()
    marks["compile_or_load"] = time.time() - t
    marks["step_cache"] = {k: cache[k] - before[k] for k in cache}
    for name in ("step_1", "step_2", "step_3"):
        t = time.time()
        state, metrics = step(state, batch)
        float(metrics["loss"])
        marks[name] = time.time() - t
    record = {"cell": cell_name, "factors": factors,
              "device": devices[0].device_kind,
              **{k: round(v, 2) if isinstance(v, float) else v
                 for k, v in marks.items()}}
    line = json.dumps(record)
    print(line, flush=True)
    out = os.path.join(ROOT, "chiprun_out", "pr37_compact")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, "setup_probe.jsonl"), "a") as f:
        f.write(line + "\n")


if __name__ == "__main__":
    main(*sys.argv[1:3])
