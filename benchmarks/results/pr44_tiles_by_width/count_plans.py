"""Which `_TILES` entry each flash call of a cell's gradient takes: the cell's
`loss_fn` traced (nothing compiled, nothing run; any host) at the cell's own
configuration and shapes, and the counter `ray_tpu_flash_tile_plans_total`
read before and after.

    JAX_PLATFORMS=cpu python3 benchmarks/results/pr44_tiles_by_width/count_plans.py <cell> ...

One line a cell: {"<kernel> <tile> <widths>": traces}. A call site's forward
is traced twice: the function and its `custom_vjp` forward rule."""
import dataclasses
import importlib
import json
import os
import sys

sys.path.insert(0, os.getcwd())
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from chipbench import catalog  # noqa: E402
from ray_tpu.util.metrics import registry_snapshot  # noqa: E402


def counts():
    return {" ".join(v["tags"][t] for t in ("kernel", "tile", "widths")):
            v["value"] for m in registry_snapshot()
            if m["name"] == "ray_tpu_flash_tile_plans_total"
            for v in m["values"]}


man = catalog.load_manifest()
for cell in sys.argv[1:]:
    r = catalog.resolve_cell(man, cell, "end_to_end")
    t = r["traffic"]
    mod_name, preset = r["model"]["entry"].split(":")
    mod = importlib.import_module(mod_name)
    cfg = dataclasses.replace(getattr(mod, preset)(), attention="flash",
                              remat=t["remat"])
    params = jax.eval_shape(lambda: mod.init(jax.random.PRNGKey(0), cfg))
    batch = {"tokens": jax.ShapeDtypeStruct((t["batch"], t["seq"] + 1),
                                            jnp.int32)}

    def loss(p, b):
        out = mod.loss_fn(p, b, cfg, None)
        return out[0] if isinstance(out, tuple) else out
    before = counts()
    try:
        jax.make_jaxpr(jax.grad(loss))(params, batch)
    except Exception as e:
        print(cell, "trace failed", repr(e)[:300])
        continue
    print(cell, json.dumps({k: n - before.get(k, 0)
                            for k, n in sorted(counts().items())
                            if n != before.get(k, 0)}))
