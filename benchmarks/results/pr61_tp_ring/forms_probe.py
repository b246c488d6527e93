"""`gpt2l-tp4`'s own train step with the `tp` reduction in each of three
forms, ONE process on the cell's four chips: median step time, then a 4-step
device profile reduced as the benchmark reduces its own (`chipbench/
trace_reduce.py`): device ms a step, the share of the window in which a
collective runs and no compute does, the `collective-permute-done` waits by
loop body (the step's own scope table: `forward`, `backward` with the
recompute beside it) and sub-layer.

    python3 benchmarks/results/pr61_tp_ring/forms_probe.py <steps> <seed> <form> ...

Forms: `whole` (the parent's: size − 1 hops of the whole partial to the next
rank), `one_way` (reduce-scatter and all-gather of `size` chunks, every hop
to rank + 1: written here, the tree never shipped it), `two_way` (the tree's
`layers.exchange_sum`: half-chunks on both ring directions),
`two_way_copied` (the same without its `optimization_barrier`: the compiler
copies the whole partial ahead of the all-gather's writes). Prints one JSON
line a form and appends it to chiprun_out/pr61_tp_ring/forms_probe.jsonl.
`PROBE_TINY=1` rehearses on the CPU (tiny preset, `tp` 4 of the virtual
devices, no profile)."""
import collections
import dataclasses
import functools
import gc
import glob
import importlib
import json
import math
import os
import shutil
import statistics
import sys
import time

ROOT = os.getcwd()
sys.path.insert(0, ROOT)
TINY = os.environ.get("PROBE_TINY") == "1"
if TINY:
    os.environ.setdefault("XLA_FLAGS",
                          "--xla_force_host_platform_device_count=4")

import jax  # noqa: E402

from chipbench import catalog, flops, generate, trace_reduce  # noqa: E402
from ray_tpu.models import layers  # noqa: E402
from ray_tpu.parallel import compile_watch  # noqa: E402
from ray_tpu.parallel.mesh import MeshConfig, create_mesh  # noqa: E402
from ray_tpu.parallel.train_step import (  # noqa: E402
    default_optimizer,
    make_train_state,
    make_train_step,
)

CELL = "gpt2l-tp4"
OUT = os.path.join(ROOT, "chiprun_out", "pr61_tp_ring")


def whole(partial, axis_name):
    n = jax.lax.axis_size(axis_name)
    perm = [(i, (i + 1) % n) for i in range(n)]
    total = moving = partial
    for _ in range(n - 1):
        moving = jax.lax.ppermute(moving, axis_name, perm)
        total = total + moving
    return total


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def one_way(partial, axis_name):
    n = jax.lax.axis_size(axis_name)
    rank = jax.lax.axis_index(axis_name)
    pieces = partial.reshape(n, -1, partial.shape[-1])
    perm = [(i, (i + 1) % n) for i in range(n)]

    def take(step):
        return jax.lax.dynamic_index_in_dim(pieces, (rank - step) % n,
                                            keepdims=False)

    moving = take(0)
    for step in range(1, n):
        moving = jax.lax.ppermute(moving, axis_name, perm) + take(step)
    pieces, moving = jax.lax.optimization_barrier((pieces, moving))
    for step in range(n - 1, 2 * n - 1):
        pieces = jax.lax.dynamic_update_index_in_dim(
            pieces, moving, (rank - step) % n, 0)
        if step < 2 * n - 2:
            moving = jax.lax.ppermute(moving, axis_name, perm)
    return pieces.reshape(partial.shape)


one_way.defvjp(lambda p, axis_name: (one_way(p, axis_name), None),
               lambda axis_name, _, ct: (one_way(ct, axis_name),))
FORMS = {"whole": whole, "one_way": one_way, "two_way": layers.exchange_sum,
         "two_way_copied": layers.exchange_sum}
BARRIER = jax.lax.optimization_barrier


def run(form, steps, seed):
    cell = catalog.resolve_cell(catalog.load_manifest(), CELL, "end_to_end")
    traffic = cell["traffic"]
    module_name, preset = cell["model"]["entry"].split(":")
    module = importlib.import_module(module_name)
    cfg = dataclasses.replace(getattr(module, preset)(),
                              attention=traffic["attention"],
                              remat=traffic["remat"])
    if TINY:
        cfg = dataclasses.replace(module.gpt2_tiny(), remat=traffic["remat"])
        traffic = dict(traffic, seq=64, batch=4)
    devices = jax.local_devices()[:math.prod(traffic["mesh"].values())]
    mesh = create_mesh(MeshConfig(**traffic["mesh"]), devices=devices)
    opt = default_optimizer(**traffic["optimizer"])
    layers.exchange_sum = FORMS[form]     # what `gpt2._tp_blocks` reads
    jax.lax.optimization_barrier = (lambda x: x) if form.endswith("_copied") \
        else BARRIER
    step = make_train_step(lambda p, b: module.loss_fn(p, b, cfg, mesh),
                           opt, mesh)
    rows = generate.token_rows(
        traffic, cfg.vocab_size if TINY
        else flops.padded_vocab(cell["model"]["vocab_size"]), seed)
    state = make_train_state(lambda rng: module.init(rng, cfg),
                             jax.random.PRNGKey(seed), opt, mesh,
                             module.partition_specs(cfg))
    batch = traffic["batch"]
    record = {"form": form, "cell": CELL, "seed": seed,
              "device": devices[0].device_kind, "chips": len(devices),
              "step_ms": [], "loss": []}
    n = 0

    def advance():
        nonlocal state, n
        at = (n * batch) % (len(rows) - batch + 1)
        n += 1
        t0 = time.perf_counter()
        state, metrics = step(state, {"tokens": rows[at:at + batch]})
        record["loss"].append(round(float(metrics["loss"]), 5))
        record["step_ms"].append(round(1e3 * (time.perf_counter() - t0), 2))

    for _ in range(steps):
        advance()
    record["first_step_s"] = round(record["step_ms"][0] / 1e3, 2)
    record["median_step_ms"] = statistics.median(record["step_ms"][steps // 2:])
    table = compile_watch.compiled("train_step").scope_table() or {}
    if not TINY:
        trace = os.path.join(OUT, "trace")
        shutil.rmtree(trace, ignore_errors=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(trace, profiler_options=options)
        for _ in range(5):          # the reduction keeps whole periods: 4
            advance()
        jax.profiler.stop_trace()
        files = glob.glob(os.path.join(trace, "**", "*.xplane.pb"),
                          recursive=True)
        summary = trace_reduce.reduce_trace(
            trace_reduce.load_xplane(files[0], ()), ())
        traced = summary["steps"]
        worst = max(summary["devices"].values(),
                    key=lambda d: d["collective_exposed_ns"] / d["window_ns"])
        kinds, waits = collections.Counter(), collections.Counter()
        for text, seconds in summary["per_op_s"].items():
            parsed = trace_reduce._parse(text)
            opcode = parsed[2] if parsed else "?"
            if opcode.startswith("collective-permute") or opcode in (
                    "all-reduce", "copy", "while"):
                kinds[opcode] += 1e3 * seconds / traced
            if opcode == "collective-permute-done":
                scopes, phase = table.get(parsed[0], ((), None))
                part = next((s for s in reversed(scopes)
                             if s in ("attention", "mlp")), "?")
                waits[f"{phase}:{part}"] += 1e3 * seconds / traced
        top = sorted(summary["per_op_s"].items(), key=lambda kv: -kv[1])[:12]
        record.update(
            traced_steps=traced,
            device_ms=round(1e3 * summary["busy_s"] / traced, 3),
            exposed_share=round(100 * worst["collective_exposed_ns"]
                                / worst["window_ns"], 3),
            exposed_ms=round(worst["collective_exposed_ns"] / traced / 1e6, 3),
            collective_ms=round(worst["collective_ns"] / traced / 1e6, 3),
            ms_by_opcode={k: round(v, 3) for k, v in kinds.items()},
            done_wait_ms={k: round(v, 3) for k, v in sorted(waits.items())},
            top=[[round(1e3 * s / traced, 3),
                  trace_reduce.short_op_name(t, 90)] for t, s in top])
        shutil.rmtree(trace, ignore_errors=True)
    del state, step
    gc.collect()
    os.makedirs(OUT, exist_ok=True)
    line = json.dumps(record)
    print(line, flush=True)
    with open(os.path.join(OUT, "forms_probe.jsonl"), "a") as f:
        f.write(line + "\n")


if __name__ == "__main__":
    for name in sys.argv[3:]:
        run(name, int(sys.argv[1]), int(sys.argv[2]))
