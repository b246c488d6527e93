"""By step and by routed LAYER (a prediction module's is the last): how many
assignments reached an expert held here and whether the layer ran on its
bounded prefix — what decides where a share's bound has to lie
(`layers.moe._BOUND_FACTORS`). `benchmarks/step_counters.py`'s loop, state and
tokens (a benchmark run's of that `--seed`), with `layers.share_metrics`
wrapped so that the step's metrics carry each layer's count beside the sums;
any cell whose model holds a share of its experts.

    python3 benchmarks/held_by_layer.py <cell> <steps> [<field>=<number> ...] <seed> [<seed> ...]

from the repo root, through the chip tool; a `<field>=<number>` sets that
field of the preset (`eh_std=4`). One JSON line a seed, appended to
chiprun_out/held_by_layer/held_by_layer.jsonl. `PROBE_TINY=1` rehearses on
the CPU with the cell's tiny preset."""
import dataclasses
import importlib
import json
import math
import os
import sys
import time

ROOT = os.getcwd()
sys.path.insert(0, ROOT)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from chipbench import catalog, flops, generate  # noqa: E402
from ray_tpu.models import layers as L  # noqa: E402
from ray_tpu.models.layers import ends  # noqa: E402
from ray_tpu.parallel.mesh import MeshConfig, create_mesh  # noqa: E402
from ray_tpu.parallel.train_step import (  # noqa: E402
    default_optimizer,
    make_train_state,
    make_train_step,
)

TINY = os.environ.get("PROBE_TINY") == "1"
_share_metrics = L.share_metrics


def by_layer(loss, counts, compact, *, tokens, cfg):
    held = jnp.sum(counts[:, cfg.first:cfg.first + cfg.stacked], axis=-1)
    return dict(_share_metrics(loss, counts, compact, tokens=tokens, cfg=cfg),
                **{f"held_{i}": held[i] for i in range(held.shape[0])},
                **{f"compact_{i}": compact[i]
                   for i in range(compact.shape[0])})


def main(cell_name, steps, seeds, overrides):
    # a model file reads `L.share_metrics`, `share_loss` its own module's
    L.share_metrics = ends.share_metrics = by_layer
    cell = catalog.resolve_cell(catalog.load_manifest(), cell_name,
                                "end_to_end")
    traffic = cell["traffic"]
    module_name, preset = cell["model"]["entry"].split(":")
    module = importlib.import_module(module_name)
    cfg = dataclasses.replace(getattr(module, preset)(),
                              attention=traffic["attention"],
                              remat=traffic["remat"], **dict(overrides))
    vocab = flops.padded_vocab(cell["model"]["vocab_size"])
    if TINY:
        tiny = next(n for n in dir(module) if n.endswith("_tiny"))
        cfg = dataclasses.replace(getattr(module, tiny)(),
                                  remat=traffic["remat"], **dict(overrides))
        traffic, vocab = dict(traffic, seq=128), cfg.vocab_size
    devices = jax.local_devices()[:math.prod(traffic["mesh"].values())]
    mesh = create_mesh(MeshConfig(**traffic["mesh"]), devices=devices)
    opt = default_optimizer(**traffic["optimizer"])
    step = make_train_step(lambda p, b: module.loss_fn(p, b, cfg, mesh),
                           opt, mesh)
    out = os.path.join(ROOT, "chiprun_out", "held_by_layer")
    os.makedirs(out, exist_ok=True)
    for seed in seeds:
        rows = generate.token_rows(traffic, vocab, seed)
        state = make_train_state(lambda rng: module.init(rng, cfg),
                                 jax.random.PRNGKey(seed), opt, mesh,
                                 module.partition_specs(cfg))
        batch = traffic["batch"]
        record = {"cell": cell_name, "seed": seed, **dict(overrides),
                  "device": devices[0].device_kind,
                  "rows_a_layer": batch * traffic["seq"] * cfg.moe.top_k,
                  "step_ms": []}
        for i in range(steps):
            at = (i * batch) % (len(rows) - batch + 1)
            t0 = time.perf_counter()
            state, metrics = step(state, {"tokens": rows[at:at + batch]})
            metrics = {k: float(v) for k, v in metrics.items()
                       if getattr(v, "ndim", 0) == 0}
            record["step_ms"].append(
                round(1e3 * (time.perf_counter() - t0), 1))
            for k, v in metrics.items():
                if k.startswith(("held_", "compact_")):
                    record.setdefault(k, []).append(int(v))
        del state
        line = json.dumps(record)
        print(line, flush=True)
        with open(os.path.join(out, "held_by_layer.jsonl"), "a") as f:
            f.write(line + "\n")


if __name__ == "__main__":
    sets = [a.split("=") for a in sys.argv[3:] if "=" in a]
    main(sys.argv[1], int(sys.argv[2]),
         [int(a) for a in sys.argv[3:] if "=" not in a],
         [(k, int(v) if v.isdigit() else float(v)) for k, v in sets])
