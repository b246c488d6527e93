"""A cell's own train step in a bare loop on the chip, then a 3-step device
profile by operation, each operation under the `jax.named_scope` its HLO
instruction's metadata names (the profiler's event text carries no scope;
`compiled.as_text()` does), and the counters of every step:

    python3 benchmarks/results/pr37_compact/step_profile.py <cell> <steps before> <seed> [whole]

`whole`: the share's bound switched off (`layers.assignment_bounds` → none),
the parent's path on the same tree. One process. Prints one JSON line and
appends it to chiprun_out/pr37_compact/step_profile.jsonl; the per-operation
times go to chiprun_out/pr37_compact/per_op_<cell>_<variant>.json.
`PROBE_TINY=1` rehearses it on the CPU with the cell's tiny preset."""
import dataclasses
import glob
import importlib
import json
import math
import os
import re
import shutil
import sys
import time

ROOT = os.getcwd()
sys.path.insert(0, ROOT)

import jax  # noqa: E402
import numpy as np  # noqa: E402

from chipbench import catalog, flops, generate, trace_reduce  # noqa: E402
from ray_tpu.models import layers  # noqa: E402
from ray_tpu.parallel.mesh import MeshConfig, create_mesh  # noqa: E402
from ray_tpu.parallel.train_step import (  # noqa: E402
    default_optimizer,
    make_train_state,
    make_train_step,
)

TINY = os.environ.get("PROBE_TINY") == "1"
SCOPES = ("dispatch", "experts", "combine", "shared_expert", "router",
          "loss_tail", "attention", "attn", "mamba", "moe")
OUT = os.path.join(ROOT, "chiprun_out", "pr37_compact")


def scope_of(op_name: str) -> str:
    parts = op_name.split("/")
    return next((s for s in SCOPES[:5] if s in parts),
                next((s for s in SCOPES[5:] if s in parts), "-"))


def scopes_by_instruction(text: str) -> dict:
    """HLO instruction name -> the scope in its metadata's op_name."""
    found = {}
    for m in re.finditer(r'^\s*(?:ROOT )?%?([\w.\-]+) = .*?op_name="([^"]*)"',
                         text, re.M):
        found[m.group(1)] = scope_of(m.group(2))
    return found


def main(cell_name, before, seed, whole):
    if whole:
        layers.assignment_bounds = lambda *a: ()
    cell = catalog.resolve_cell(catalog.load_manifest(), cell_name,
                                "end_to_end")
    traffic = cell["traffic"]
    module_name, preset = cell["model"]["entry"].split(":")
    module = importlib.import_module(module_name)
    cfg = dataclasses.replace(getattr(module, preset)(),
                              attention=traffic["attention"],
                              remat=traffic["remat"])
    if TINY:
        tiny = next(n for n in dir(module) if n.endswith("_tiny"))
        cfg = dataclasses.replace(getattr(module, tiny)(),
                                  remat=traffic["remat"])
        traffic = dict(traffic, seq=cfg.block_size if hasattr(
            cfg, "block_size") else 64)
    devices = jax.local_devices()[:math.prod(traffic["mesh"].values())]
    mesh = create_mesh(MeshConfig(**traffic["mesh"]), devices=devices)
    opt = default_optimizer(**traffic["optimizer"])
    step = make_train_step(lambda p, b: module.loss_fn(p, b, cfg, mesh),
                           opt, mesh)
    rows = generate.token_rows(
        traffic, cfg.vocab_size if TINY
        else flops.padded_vocab(cell["model"]["vocab_size"]), seed)
    state = make_train_state(lambda rng: module.init(rng, cfg),
                             jax.random.PRNGKey(seed), opt, mesh,
                             module.partition_specs(cfg))
    batch = traffic["batch"]
    record = {"cell": cell_name, "seed": seed,
              "variant": "whole" if whole else "bounded",
              "device": devices[0].device_kind, "step_ms": []}
    n = 0

    def advance():
        nonlocal state, n
        at = (n * batch) % (len(rows) - batch + 1)
        n += 1
        t0 = time.perf_counter()
        state, metrics = step(state, {"tokens": rows[at:at + batch]})
        metrics = {k: float(v) for k, v in metrics.items()
                   if getattr(v, "ndim", 0) == 0}
        record["step_ms"].append(round(1e3 * (time.perf_counter() - t0), 2))
        for k, v in metrics.items():
            record.setdefault(k, []).append(v)

    for _ in range(before):
        advance()
    compiled = step.lower(
        state, {"tokens": rows[:batch]}).compile()
    plan = compiled.memory_analysis()
    record["plan_gb"] = (plan.argument_size_in_bytes
                         + plan.temp_size_in_bytes) / 1e9
    scopes = scopes_by_instruction(compiled.as_text())
    os.makedirs(OUT, exist_ok=True)
    if not TINY:
        trace = os.path.join(OUT, "trace")
        shutil.rmtree(trace, ignore_errors=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(trace, profiler_options=options)
        for _ in range(3):
            advance()
        jax.profiler.stop_trace()
        files = glob.glob(os.path.join(trace, "**", "*.xplane.pb"),
                          recursive=True)
        summary = trace_reduce.reduce_trace(
            trace_reduce.load_xplane(files[0], ()), ())
        steps = summary["steps"]
        per_op = {name: 1e3 * s / steps
                  for name, s in summary["per_op_s"].items()}
        by_scope, named = {}, {}
        for text, ms in per_op.items():
            m = re.match(r"\s*%?([\w.\-]+)", text)
            name = m.group(1) if m else text[:40]
            scope = scopes.get(name, "?")
            by_scope[scope] = by_scope.get(scope, 0.0) + ms
            named[name] = [round(ms, 4), scope,
                           trace_reduce.short_op_name(text, 140)]
        record.update(
            traced_steps=steps,
            device_ms=round(1e3 * summary["busy_s"] / steps, 3),
            ms_by_scope={k: round(v, 3) for k, v in sorted(
                by_scope.items(), key=lambda kv: -kv[1])},
            top_ops=sorted(named.values(), key=lambda v: -v[0])[:70])
        with open(os.path.join(
                OUT, f"per_op_{cell_name}_{record['variant']}.json"),
                "w") as f:
            json.dump(named, f)
        shutil.rmtree(trace, ignore_errors=True)
    line = json.dumps(record)
    print(line, flush=True)
    with open(os.path.join(OUT, "step_profile.jsonl"), "a") as f:
        f.write(line + "\n")


if __name__ == "__main__":
    main(sys.argv[1], int(sys.argv[2]), int(sys.argv[3]),
         "whole" in sys.argv[4:])
