"""By step and by routed LAYER: how many assignments reached an expert held
here, and whether the layer ran on the bounded prefix — what decides where
a share's bound has to lie (`layers._BOUND_FACTOR`). The cell's own step in
a bare loop with the model's loss rebuilt around `_forward` (the step's
`metrics` carry the sums over the layers only), one process on the chip:

    python3 benchmarks/results/pr37_compact/held_by_layer.py <cell> <steps> <seed> [<seed> ...]

Prints one JSON line a seed and appends it to
chiprun_out/pr37_compact/held_by_layer.jsonl. `PROBE_TINY=1` rehearses it on
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
from ray_tpu.parallel.mesh import MeshConfig, create_mesh  # noqa: E402
from ray_tpu.parallel.train_step import (  # noqa: E402
    default_optimizer,
    make_train_state,
    make_train_step,
)

TINY = os.environ.get("PROBE_TINY") == "1"


def main(cell_name, steps, seeds):
    cell = catalog.resolve_cell(catalog.load_manifest(), cell_name,
                                "end_to_end")
    traffic = cell["traffic"]
    module_name, preset = cell["model"]["entry"].split(":")
    module = importlib.import_module(module_name)
    cfg = dataclasses.replace(getattr(module, preset)(),
                              attention=traffic["attention"],
                              remat=traffic["remat"])
    vocab = flops.padded_vocab(cell["model"]["vocab_size"])
    if TINY:
        tiny = next(n for n in dir(module) if n.endswith("_tiny"))
        cfg = dataclasses.replace(getattr(module, tiny)(),
                                  remat=traffic["remat"])
        traffic, vocab = dict(traffic, seq=128), cfg.vocab_size
    devices = jax.local_devices()[:math.prod(traffic["mesh"].values())]
    mesh = create_mesh(MeshConfig(**traffic["mesh"]), devices=devices)
    opt = default_optimizer(**traffic["optimizer"])

    def loss_fn(params, batch):
        tokens, targets = batch["tokens"][:, :-1], batch["tokens"][:, 1:]
        logits, counts, compact = module._forward(params, tokens, cfg, mesh)
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        tl = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
        loss = jnp.mean(lse - tl)
        held = jnp.sum(counts[:, cfg.first:cfg.first + cfg.moe.stacked], -1)
        return loss, {"loss": loss,
                      **{f"held_{i}": held[i] for i in range(held.shape[0])},
                      **{f"compact_{i}": compact[i]
                         for i in range(compact.shape[0])}}

    step = make_train_step(loss_fn, opt, mesh)
    out = os.path.join(ROOT, "chiprun_out", "pr37_compact")
    os.makedirs(out, exist_ok=True)
    for seed in seeds:
        rows = generate.token_rows(traffic, vocab, seed)
        state = make_train_state(lambda rng: module.init(rng, cfg),
                                 jax.random.PRNGKey(seed), opt, mesh,
                                 module.partition_specs(cfg))
        batch = traffic["batch"]
        record = {"cell": cell_name, "seed": seed,
                  "device": devices[0].device_kind,
                  "rows_a_layer": batch * traffic["seq"] * cfg.top_k,
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
    main(sys.argv[1], int(sys.argv[2]), [int(s) for s in sys.argv[3:]])
