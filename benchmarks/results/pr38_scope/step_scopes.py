"""A cell's whole train step compiled for a DESCRIBED v5e (no chip): is it
the parent's program, and what does its scope table cover?

    JAX_PLATFORMS=cpu python3 benchmarks/results/pr38_scope/step_scopes.py <tree> <cell> <out.json> [per_op.json]

`<tree>` is a checkout (the program and the benchmark's files are read from
it). Writes one JSON object: `sha256` of the optimized HLO with
`metadata={…}`, the source tables, the Mosaic kernels' source locations AND
every name taken out (`benchmarks/step_hlo_compare.strip`; a kernel's MLIR
without its module's symbol; every instruction named by the order it is
defined in), so that a parent without the flash calls' names and a change
with them give one hash; `flash_calls`, the
custom calls' names as the text has them; and, where the tree's
`compile_watch` has a table, the instructions by phase and scope and every
instruction the device runs on its own that stays unscoped, largest
result first (bytes written stand in for time here; the chip's answer is
`train_step.unscoped_share`) — beside the milliseconds a step of
`per_op.json` (`{name: [ms, ...]}`, a chip profile of the same program, as
PR 37's `step_profile.py` wrote) where given. A compile is not a chip run: no time here is this program's."""
import base64
import collections
import dataclasses
import hashlib
import importlib
import json
import math
import os
import re
import sys
import time

tree, cell, out = sys.argv[1:4]
per_op_file = sys.argv[4] if len(sys.argv) > 4 else None
repo = os.getcwd()
sys.path.insert(0, os.path.abspath(tree))
sys.path.insert(1, os.path.join(repo, "benchmarks"))
os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.chdir(tree)
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.experimental import topologies  # noqa: E402
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P  # noqa: E402

import step_hlo_compare  # noqa: E402
from chipbench import catalog  # noqa: E402
from ray_tpu.parallel import compile_watch  # noqa: E402
from ray_tpu.parallel.mesh import AXIS_ORDER  # noqa: E402
from ray_tpu.parallel.train_step import (  # noqa: E402
    TrainState,
    default_optimizer,
    make_train_step,
)

jax.config.update("jax_enable_compilation_cache", False)
resolved = catalog.resolve_cell(catalog.load_manifest(), cell, "end_to_end")
traffic = resolved["traffic"]
module_name, preset = resolved["model"]["entry"].split(":")
module = importlib.import_module(module_name)
cfg = dataclasses.replace(getattr(module, preset)(), attention="flash",
                          remat=traffic["remat"])
axes = {"dp": 1, "tp": 1, **traffic["mesh"]}
topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
mesh = Mesh(np.array(topo.devices[:math.prod(axes.values())]).reshape(
    tuple(axes.get(a, 1) for a in AXIS_ORDER)), AXIS_ORDER)
opt = default_optimizer(**traffic["optimizer"])


def on(spec):
    return NamedSharding(mesh, spec)


params = jax.tree_util.tree_map(
    lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=on(s)),
    jax.eval_shape(lambda: module.init(jax.random.PRNGKey(0), cfg)),
    module.partition_specs(cfg))
by_shape = {a.shape: a.sharding for a in jax.tree_util.tree_leaves(params)}
opt_state = jax.tree_util.tree_map(
    lambda a: jax.ShapeDtypeStruct(
        a.shape, a.dtype,
        sharding=by_shape.get(a.shape, on(P())) if a.ndim else on(P())),
    jax.eval_shape(opt.init, params))
state = TrainState(step=jax.ShapeDtypeStruct((), jnp.int32, sharding=on(P())),
                   params=params, opt_state=opt_state)
tokens = jax.ShapeDtypeStruct((traffic["batch"], traffic["seq"] + 1),
                              jnp.int32, sharding=on(P(("dp",), "sp")))
step = make_train_step(lambda p, b: module.loss_fn(p, b, cfg, mesh), opt, mesh)
t0 = time.time()
text = step.lower(state, {"tokens": tokens}).compile().as_text()
compile_s = time.time() - t0
if os.environ.get("STEP_SCOPES_TEXT"):          # the whole text, to read by hand
    with open(os.environ["STEP_SCOPES_TEXT"], "w") as f:
        f.write(text)

INSTRUCTION = re.compile(
    r"^\s*(?:ROOT )?%?([\w.\-]+) = (\S+?)(?:\{[^ ]*)? ([\w\-]+)\(", re.M)


def _kernel_without_its_name(match) -> str:
    """A Mosaic kernel's serialized MLIR as the sha256 of its text printed
    without locations (as `step_hlo_compare` holds it) and without the
    module's symbol, which is `pallas_call`'s `name`."""
    from jax._src.interpreters import mlir as jmlir
    from jax._src.lib.mlir import ir

    context = jmlir.make_ir_context()
    context.allow_unregistered_dialects = True
    with context:
        asm = ir.Module.parse(base64.b64decode(match.group(1))) \
            .operation.get_asm(enable_debug_info=False)
    asm = re.sub(r"^module @\S+", "module @kernel", asm)
    return '"body":"mlir-sha256:%s"' % hashlib.sha256(asm.encode()).hexdigest()


def same_program_digest(text: str):
    """(sha256, names of the Mosaic custom calls): the text without
    metadata, source tables, kernel source locations and names."""
    text = re.sub(r'"body":"([A-Za-z0-9+/=]+)"(?=,"needs_layout_passes")',
                  _kernel_without_its_name, text)
    stripped = step_hlo_compare.strip(text)
    calls = [m.group(1) for m in re.finditer(
        r'^\s*(?:ROOT )?%([\w.\-]+) = [^\n]*custom_call_target="tpu_custom_call"',
        stripped, re.M)]
    # naming three calls renumbers every instruction that shared the prefix
    # they had by accident (`closed_call.N` in `gpt2l-dp2tp2`): every
    # instruction goes by the order it is defined in
    order = {name: f"%i{n}" for n, name in enumerate(re.findall(
        r"^\s*(?:ROOT )?(%[\w.\-]+) = ", stripped, re.M))}
    stripped = re.sub(r"%[\w.\-]+", lambda m: order.get(m.group(0),
                                                         m.group(0)), stripped)
    if os.environ.get("STEP_SCOPES_DUMP"):      # to diff two sides by hand
        with open(os.environ["STEP_SCOPES_DUMP"], "w") as f:
            f.write(stripped)
    return hashlib.sha256(stripped.encode()).hexdigest(), calls


digest, calls = same_program_digest(text)
record = {"cell": cell, "tree": tree, "sha256": digest,
          "compile_s": round(compile_s, 1), "custom_calls": len(calls),
          "flash_calls": sorted(c for c in calls if re.match(
              r"(flash|closed_call|checkpoint|attention|attn|jvp)", c))}
BYTES = {"pred": 1, "s8": 1, "u8": 1, "bf16": 2, "f16": 2, "s32": 4,
         "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8}


def result_bytes(shape: str) -> int:
    return sum(BYTES.get(kind, 4) * math.prod(map(int, dims.split(",")))
               for kind, dims in re.findall(r"\b([a-z]+\d+|pred)\[([\d,]+)\]",
                                            shape))


def timed_instructions(text: str):
    """(name, result, opcode, op_name) of every instruction the device
    runs as an operation of its own: those of the computations that no
    `calls=` (a fusion's body) and no `to_apply=` (a reducer) names."""
    inner = set(re.findall(r"(?:calls|to_apply)=%?([\w.\-]+)", text))
    inside = None
    for line in text.splitlines():
        head = re.match(r"^(?:ENTRY )?%?([\w.\-]+) \(.*\{\s*$", line)
        if head:
            inside = head.group(1)
            continue
        m = INSTRUCTION.match(line)
        if m and inside not in inner:
            op_name = re.search(r'op_name="([^"]*)"', line)
            yield (*m.groups(), op_name.group(1) if op_name else None)


table_of = getattr(compile_watch, "scope_table_of", None)
table = table_of(text) if table_of else None
if table is not None:
    per_op = {}
    if per_op_file:
        with open(os.path.join(repo, per_op_file)) as f:
            per_op = {k: v[0] for k, v in json.load(f).items()}
    skip = {"parameter", "constant", "get-tuple-element", "tuple", "bitcast"}
    by, ms_by = collections.Counter(), collections.Counter()
    unscoped, all_bytes = [], 0
    for name, shape, opcode, op_name in timed_instructions(text):
        if opcode in skip:
            continue
        scopes, phase = table.get(name, ((), "forward"))
        key = phase + ":" + "/".join(scopes[:3])
        by[key] += 1
        ms_by[key] += per_op.get(name, 0.0)
        size = result_bytes(shape)
        all_bytes += size
        if not scopes:
            unscoped.append([size, name, opcode, shape, op_name,
                             per_op.get(name)])
    unscoped.sort(key=lambda row: -row[0])
    record.update(
        scope_names=sorted({n for scopes, _ in table.values()
                            for n in scopes}),
        instructions=sum(by.values()),
        by_phase_and_scope=dict(sorted(by.items())),
        result_gb=round(all_bytes / 1e9, 3),
        unscoped_result_gb=round(sum(row[0] for row in unscoped) / 1e9, 3),
        unscoped=len(unscoped), largest_unscoped=unscoped[:60],
        profile_ms_found=round(sum(ms_by.values()), 3),
        profile_ms_all=round(sum(per_op.values()), 3),
        profile_ms_by_phase_and_scope={
            k: round(v, 3) for k, v in sorted(ms_by.items()) if v})
else:
    record["table"] = None
with open(os.path.join(repo, out), "w") as f:
    json.dump(record, f, indent=1)
print(json.dumps({k: record[k] for k in
                  ("cell", "tree", "sha256", "compile_s", "flash_calls")}),
      flush=True)
