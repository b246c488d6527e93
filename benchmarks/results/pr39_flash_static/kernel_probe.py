"""The three flash kernels alone at the D 128 cells' shapes, one tree beside
another:

    python3 benchmarks/results/pr39_flash_static/kernel_probe.py chip <name>=<tree>[:<knob>=<value>] ...
    python3 benchmarks/results/pr39_flash_static/kernel_probe.py compile <name>=<tree>...   # no chip

`<tree>` is a checkout's root (`.`, `.bench_tree/parent`): its
`ray_tpu/ops/flash_attention.py` is loaded as a module of its own, so one
process holds both sides; `<knob>=<value>` sets a module attribute after the
import (the scratch forms this PR swept). `chip`: forward + backward of
`flash_attention` jitted at each shape, the seconds from trace to first result
(trace, lower, Mosaic, XLA), then a device profile of `REPEATS` calls read by
the calls' names (`flash_fwd` / `flash_dq` / `flash_dkv`, `flash_window_*`):
milliseconds a call. `compile`: the same programs compiled for a DESCRIBED
v5e on a CPU host — seconds only, nothing runs. One JSON line a (side, shape)
to stdout and to chiprun_out/pr39_flash_static/kernel_probe.jsonl.
`PROBE_TINY=1` rehearses on the CPU under the interpreter.

What this PR kept of its runs: `kernels_parent_change.jsonl` (the parent
beside the tree as shipped: every row of tiles written out),
`kernels_parent_rowloop.jsonl` (the parent beside the first round's tree,
which walked a whole block's rows by one loop around one row's code) and
`kernel_sweep.jsonl` — the forms swept before one was chosen, on a scratch
tree whose module had two attributes the shipped one lacks: `_WHOLE_ROWS`
(sides `u1`: the loop around one row; `u0`: the whole block written out, as
shipped) and no `MAJOR_ROWS` (sides `m1024…`: `VMEM_BUDGET_BYTES=5242880`,
which gave the 1,024-row blocks `MAJOR_ROWS` gives now; the others 2,048-row
blocks). That run's reader missed the names a bare jit gives the calls
(`jvp_flash_fwd_.1`), so its `ms_a_call` holds `no_flash_event`: the longest
events' names with their nanoseconds over the five calls."""
import glob
import importlib.util
import json
import os
import re
import shutil
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = os.getcwd()
sys.path.insert(0, ROOT)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

TINY = os.environ.get("PROBE_TINY") == "1"
OUT = os.path.join(ROOT, "chiprun_out", "pr39_flash_static")
REPEATS = 5
# cell: (B, S, H, KV, D, window) as `layers.apply_attention` hands them over
SHAPES = {
    "olmoe1l-b2s4k": (2, 4096, 16, 16, 128, None),
    "nemotronh9l-b1s8k": (1, 8192, 32, 2, 128, None),
    "smallthinker4l-b1s16k.global": (1, 16384, 28, 4, 128, None),
    "smallthinker4l-b1s16k.window": (1, 16384, 28, 4, 128, 4096),
    "gpt2s-b16": (16, 1024, 12, 12, 64, None),
}
if TINY:
    SHAPES = {"tiny.global": (1, 512, 4, 2, 128, None),
              "tiny.window": (1, 512, 4, 2, 128, 200)}
KERNEL = re.compile(r"^%?[a-z_]*?(flash_(?:window_)?(?:fwd|dq|dkv))(?=[_.\s=]|$)")


def load(name, tree):
    spec = importlib.util.spec_from_file_location(
        f"flash_{name}", os.path.join(tree, "ray_tpu/ops/flash_attention.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def program(fa, shape):
    B, S, H, KV, D, window = shape
    if TINY:
        fa.VMEM_BUDGET_BYTES = 300 * 1024

    def loss(q, k, v):
        o = fa.flash_attention(q, k, v, window=window, interpret=TINY)
        return jnp.sum(o.astype(jnp.float32))
    return jax.jit(jax.grad(loss, (0, 1, 2)))


def inputs(shape, sharding=None):
    B, S, H, KV, D, _ = shape
    dtype = jnp.float32 if TINY else jnp.bfloat16
    return [jax.ShapeDtypeStruct((B, S, h, D), dtype, sharding=sharding)
            for h in (H, KV, KV)]


def compile_only(sides):
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", False)
    for cell, shape in SHAPES.items():
        for name, fa in sides:
            t0 = time.perf_counter()
            lowered = program(fa, shape).lower(*inputs(shape, chip))
            t1 = time.perf_counter()
            compiled = lowered.compile()
            t2 = time.perf_counter()
            emit({"mode": "compile", "cell": cell, "side": name,
                  "trace_lower_s": round(t1 - t0, 3),
                  "compile_s": round(t2 - t1, 3),
                  "temp_bytes": compiled.memory_analysis().temp_size_in_bytes})


def on_chip(sides):
    jax.config.update("jax_enable_compilation_cache", False)
    device = jax.devices()[0]
    for cell, shape in SHAPES.items():
        key = jax.random.PRNGKey(0)
        args = [jax.random.normal(k, s.shape, s.dtype) for k, s in
                zip(jax.random.split(key, 3), inputs(shape))]
        results = {}
        for name, fa in sides:
            fn = program(fa, shape)
            t0 = time.perf_counter()
            lowered = fn.lower(*args)
            t1 = time.perf_counter()
            compiled = lowered.compile()
            t2 = time.perf_counter()
            out = jax.block_until_ready(compiled(*args))
            results[name] = out
            row = {"mode": "chip", "cell": cell, "side": name,
                   "device": device.device_kind,
                   "trace_lower_s": round(t1 - t0, 3),
                   "compile_s": round(t2 - t1, 3)}
            t0 = time.perf_counter()
            for _ in range(REPEATS):
                out = compiled(*args)
            jax.block_until_ready(out)
            row["host_ms_a_call"] = round(
                1e3 * (time.perf_counter() - t0) / REPEATS, 3)
            if not TINY:
                trace = os.path.join(OUT, "trace")
                shutil.rmtree(trace, ignore_errors=True)
                options = jax.profiler.ProfileOptions()
                options.python_tracer_level = 0
                jax.profiler.start_trace(trace, profiler_options=options)
                for _ in range(REPEATS):
                    out = compiled(*args)
                jax.block_until_ready(out)
                jax.profiler.stop_trace()
                row["ms_a_call"] = kernel_ms(trace)
                shutil.rmtree(trace, ignore_errors=True)
            first = results[sides[0][0]]
            row["equal_to_first_side"] = [
                bool(jnp.array_equal(a, b)) for a, b in zip(out, first)]
            emit(row)


def kernel_ms(trace):
    """Milliseconds a call by kernel name, from the first device's "XLA Ops"
    line; where no event carries a flash name, the longest events' names."""
    from jax.profiler import ProfileData
    path = glob.glob(os.path.join(trace, "**", "*.xplane.pb"),
                     recursive=True)[0]
    sums, others = {}, {}
    planes = [p for p in ProfileData.from_file(path).planes
              if re.match(r"^/device:TPU:\d+$", p.name)]
    for line in (planes[0].lines if planes else ()):
        if line.name != "XLA Ops":
            continue
        for ev in line.events:
            found = KERNEL.search(ev.name)
            into, key = (sums, found.group(1)) if found else \
                (others, ev.name[:60])
            into[key] = into.get(key, 0) + ev.duration_ns
    if not sums:
        top = sorted(others.items(), key=lambda kv: -kv[1])[:6]
        return {"no_flash_event": [[k, v] for k, v in top],
                "planes": [p.name for p in
                           ProfileData.from_file(path).planes]}
    return {k: round(v / REPEATS / 1e6, 4) for k, v in sorted(sums.items())}


def emit(row):
    os.makedirs(OUT, exist_ok=True)
    line = json.dumps(row)
    print(line, flush=True)
    with open(os.path.join(OUT, "kernel_probe.jsonl"), "a") as f:
        f.write(line + "\n")


def main(mode, *specs):
    sides = []
    for spec in specs:
        name, _, rest = spec.partition("=")
        tree, *knobs = rest.split(":")
        fa = load(name, os.path.join(ROOT, tree))
        for knob in knobs:
            key, _, value = knob.partition("=")
            setattr(fa, key, json.loads(value))
        sides.append((name, fa))
    {"chip": on_chip, "compile": compile_only}[mode](sides)


if __name__ == "__main__":
    main(*sys.argv[1:])
