"""A cell's own train step in a bare loop on the chip, then a 3-step device
profile folded over the program's own scope table
(`compile_watch.compiled("train_step").scope_table()`):

    python3 benchmarks/step_by_scope.py <cell> <steps before> <seed>

One process (it holds the cell's chips). Prints one JSON line and appends it
to chiprun_out/step_by_scope/step_by_scope.jsonl: milliseconds a step by the
innermost scope of a fixed list (`PARTS`) with each part's eight longest
instructions, by phase, by flash / ssd / gmm call name, the seconds `scope_table()` took (its re-lowering: a
compile-cache hit), and every instruction that stays unscoped with its time,
opcode and result. `PROBE_TINY=1` rehearses it on the CPU with the cell's
tiny preset (no profile there)."""
import collections
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

from chipbench import catalog, flops, generate, trace_reduce  # noqa: E402
from ray_tpu.parallel import compile_watch  # noqa: E402
from ray_tpu.parallel.mesh import MeshConfig, create_mesh  # noqa: E402
from ray_tpu.parallel.train_step import (  # noqa: E402
    default_optimizer,
    make_train_state,
    make_train_step,
)

TINY = os.environ.get("PROBE_TINY") == "1"
OUT = os.path.join(ROOT, "chiprun_out", "step_by_scope")
# the part of the model an instruction is booked under: the first of these
# found in its scopes, read from the innermost outwards
PARTS = ("optimizer", "loss_tail", "embed", "router", "dispatch", "experts",
         "combine", "shared_expert", "conv", "gate_norm", "ssd", "mamba",
         "selective_scan", "mamba1", "gmu", "diff_flash", "cross_attn",
         "delta_proj", "delta_conv", "delta_rule", "delta_gate_norm", "gdn",
         "kda_proj", "kda_conv", "kda_rule", "kda_gate_norm", "kda",
         "latent_proj", "attn_gate", "indexer", "indexer_scores", "select",
         "sparse_attn", "indexer_loss", "attention", "attn", "mlp", "moe",
         "mtp", "blocks")
KERNEL = re.compile(r"(flash_(?:window|latent|sparse)_(?:fwd|dq|dkv)|"
                    r"flash_(?:fwd|dq|dkv)|"
                    r"indexer_scores_(?:fwd|dq|dk)|sparse_select|"
                    r"sparse_mean_probs|indexer_kl_(?:fwd|bwd)|"
                    r"ssd_(?:fwd|bwd)|mamba_(?:conv|gate)_(?:fwd|bwd)|"
                    r"delta_(?:fwd|bwd)|kda_(?:fwd|bwd)|sscan_(?:fwd|bwd)|"
                    r"t?gmm)(?:\.\d+)?$")


def part_of(scopes) -> str:
    return next((s for s in reversed(scopes) if s in PARTS), "unscoped")


def main(cell_name, before, seed):
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
        traffic = dict(traffic, seq=64, mesh={"dp": 1}, batch=2)
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
              "device": devices[0].device_kind, "chips": len(devices),
              "step_ms": []}
    n = 0

    def advance():
        nonlocal state, n
        at = (n * batch) % (len(rows) - batch + 1)
        n += 1
        t0 = time.perf_counter()
        state, metrics = step(state, {"tokens": rows[at:at + batch]})
        float(metrics["loss"])
        record["step_ms"].append(round(1e3 * (time.perf_counter() - t0), 2))

    for _ in range(before):
        advance()
    t0 = time.perf_counter()
    table = compile_watch.compiled("train_step").scope_table()
    record["scope_table_s"] = round(time.perf_counter() - t0, 3)
    record["table_instructions"] = len(table)
    os.makedirs(OUT, exist_ok=True)
    if not TINY:
        trace = os.path.join(OUT, "trace")
        shutil.rmtree(trace, ignore_errors=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(trace, profiler_options=options)
        for _ in range(4):          # the reduction keeps whole periods: 3
            advance()
        jax.profiler.stop_trace()
        files = glob.glob(os.path.join(trace, "**", "*.xplane.pb"),
                          recursive=True)
        summary = trace_reduce.reduce_trace(
            trace_reduce.load_xplane(files[0], ()), ())
        steps = summary["steps"]
        by_part, by_phase = collections.Counter(), collections.Counter()
        by_part_phase, kernels = collections.Counter(), collections.Counter()
        unscoped, by_part_ops = [], collections.defaultdict(list)
        for text, seconds in summary["per_op_s"].items():
            ms = 1e3 * seconds / steps
            name = text.partition(" = ")[0].strip().lstrip("%")
            scopes, phase = table.get(name, ((), None))
            part = part_of(scopes) if scopes else "unscoped"
            phase = phase if scopes else "unscoped"
            by_part[part] += ms
            by_part_ops[part].append(
                [round(ms, 3), phase, trace_reduce.short_op_name(text, 100)])
            by_phase[phase] += ms
            by_part_phase[f"{part}:{phase}"] += ms
            kernel = KERNEL.match(name)
            if kernel:
                kernels[kernel.group(1)] += ms
            if not scopes:
                unscoped.append([round(ms, 4), name in table,
                                 trace_reduce.short_op_name(text, 120)])

        def rounded(counter):
            return {k: round(v, 3) for k, v in sorted(
                counter.items(), key=lambda kv: -kv[1])}

        record.update(
            traced_steps=steps,
            device_ms=round(1e3 * summary["busy_s"] / steps, 3),
            ms_by_part=rounded(by_part), ms_by_phase=rounded(by_phase),
            ms_by_part_and_phase=rounded(by_part_phase),
            ms_by_kernel=rounded(kernels),
            top_by_part={part: sorted(ops, key=lambda row: -row[0])[:8]
                         for part, ops in by_part_ops.items()},
            unscoped=sorted(unscoped, key=lambda row: -row[0])[:40])
        shutil.rmtree(trace, ignore_errors=True)
    line = json.dumps(record)
    print(line, flush=True)
    with open(os.path.join(OUT, "step_by_scope.jsonl"), "a") as f:
        f.write(line + "\n")


if __name__ == "__main__":
    main(sys.argv[1], int(sys.argv[2]), int(sys.argv[3]))
