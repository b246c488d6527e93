"""The flash kernels' score tiles swept at the latent cell's shapes, one
process, each form a fresh copy of this tree's `ray_tpu/ops/flash_attention.py`
with its `_TILES` set:

    python3 benchmarks/results/pr44_tiles_by_width/sweep_tiles.py chip <form> ...
    python3 benchmarks/results/pr44_tiles_by_width/sweep_tiles.py compile <form> ...   # no chip

`<form>` is `<name>=<fwd>,<dq>,<dkv>` with each tile `<rows>x<columns>`
(`filed=128x256,256x256,128x128`); the form's three tiles are set BOTH as the
(192, 128) entry and as the default, so that the equal-width call (q, k, v
all 128 wide) is read at the same tiles beside the latent one. The three
kernels are independent (each has its own tile and its own `pallas_call`;
they share the 1,024-row major block whatever the tiles here), so one form
carries one candidate of each.

`chip`: the method of `pr43_joyai/kernel_probe.py` — `[2, 8192, 32, 192 /
128]` bf16, wall clock around one jitted call, best of five — and, what that
probe cannot give, the three kernels EACH by their names from a device
profile of five calls of forward + backward (`flash_latent_fwd` / `_dq` /
`_dkv`, `flash_fwd` / …): ms a call. `compile`: the same programs compiled
for a DESCRIBED v5e on a CPU host — seconds to trace, lower and compile,
nothing runs. One JSON line a form to stdout and to
chiprun_out/pr44_tiles_by_width/sweep_tiles.jsonl. `PROBE_TINY=1` rehearses
on the CPU through the interpreter."""
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
OUT = os.path.join(ROOT, "chiprun_out", "pr44_tiles_by_width")
REPEATS = 5
B, S, H = (1, 512, 2) if TINY else (2, 8192, 32)
NOPE, ROPE, V = 128, 64, 128
KERNEL = re.compile(r"(flash_(?:latent_)?(?:fwd|dq|dkv))(?=[_.\s=]|$)")


def load(name, tiles):
    spec = importlib.util.spec_from_file_location(
        f"flash_{name}", os.path.join(ROOT, "ray_tpu/ops/flash_attention.py"))
    fa = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(fa)
    fa._TILES = {None: tiles, (NOPE + ROPE, V): tiles}
    return fa


def programs(fa):
    extra = {"interpret": True} if TINY else {}

    def latent(q, k, pe, v):
        return fa.flash_attention(q, k, v, k_shared=pe, **extra)

    def equal(q, k, v):
        return fa.flash_attention(q, k, v, **extra)

    def grad(fn, n):
        return jax.jit(jax.grad(
            lambda *a: jnp.sum(fn(*a).astype(jnp.float32)), tuple(range(n))))
    return {"latent_fwd": jax.jit(latent), "latent_fwd_bwd": grad(latent, 4),
            "equal_fwd": jax.jit(equal), "equal_fwd_bwd": grad(equal, 3)}


def shapes(sharding=None):
    dtype = jnp.float32 if TINY else jnp.bfloat16
    q, k, pe, v = (jax.ShapeDtypeStruct(s, dtype, sharding=sharding)
                   for s in ((B, S, H, NOPE + ROPE), (B, S, H, NOPE),
                             (B, S, ROPE), (B, S, H, V)))
    return {"latent": (q, k, pe, v), "equal": (k, k, v)}


def best_ms(fn, args):
    jax.block_until_ready(fn(*args))
    times = []
    for _ in range(REPEATS):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append(time.perf_counter() - t0)
    return round(1e3 * min(times), 4)


def kernel_ms(fn, args):
    """ms a call of each flash kernel, by name, from the first device's
    "XLA Ops" line over `REPEATS` calls of `fn`."""
    from jax.profiler import ProfileData
    trace = os.path.join(OUT, "trace")
    shutil.rmtree(trace, ignore_errors=True)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(trace, profiler_options=options)
    for _ in range(REPEATS):
        out = fn(*args)
    jax.block_until_ready(out)
    jax.profiler.stop_trace()
    path = glob.glob(os.path.join(trace, "**", "*.xplane.pb"),
                     recursive=True)[0]
    sums = {}
    planes = [p for p in ProfileData.from_file(path).planes
              if re.match(r"^/device:TPU:\d+$", p.name)]
    for line in (planes[0].lines if planes else ()):
        if line.name != "XLA Ops":
            continue
        for ev in line.events:
            found = KERNEL.search(ev.name)
            if found:
                sums[found.group(1)] = sums.get(found.group(1), 0) \
                    + ev.duration_ns
    shutil.rmtree(trace, ignore_errors=True)
    return {k: round(v / REPEATS / 1e6, 4) for k, v in sorted(sums.items())}


def on_chip(forms):
    jax.config.update("jax_enable_compilation_cache", False)
    keys = jax.random.split(jax.random.PRNGKey(0), 4)
    given = shapes()
    q, k, pe, v = (jax.random.normal(key, s.shape, s.dtype)
                   for key, s in zip(keys, given["latent"]))
    args = {"latent": (q, k, pe, v), "equal": (q[..., :NOPE], k, v)}
    for name, tiles in forms:
        fns = programs(load(name, tiles))
        row = {"mode": "chip", "form": name, "tiles": tiles,
               "shape": [B, S, H, NOPE, ROPE, V],
               "device": jax.devices()[0].device_kind}
        for what, fn in fns.items():
            side = what.split("_")[0]
            t0 = time.perf_counter()
            try:
                row[f"{what}_ms"] = best_ms(fn, args[side])
            except Exception as e:   # the chip's compiler refuses the form
                row[f"{what}_refused"] = str(e).splitlines()[0][:300]
                continue
            row[f"{what}_first_s"] = round(time.perf_counter() - t0, 2)
            if what.endswith("bwd") and not TINY:
                row[f"{side}_kernels_ms"] = kernel_ms(fn, args[side])
        emit(row)


def compile_only(forms):
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    given = shapes(SingleDeviceSharding(topo.devices[0]))
    jax.config.update("jax_enable_compilation_cache", False)
    for name, tiles in forms:
        row = {"mode": "compile", "form": name, "tiles": tiles,
               "shape": [B, S, H, NOPE, ROPE, V]}
        for what, fn in programs(load(name, tiles)).items():
            if not what.endswith("bwd"):
                continue
            t0 = time.perf_counter()
            try:
                lowered = fn.lower(*given[what.split("_")[0]])
                t1 = time.perf_counter()
                lowered.compile()
                row[f"{what}_s"] = [round(t1 - t0, 2),
                                    round(time.perf_counter() - t1, 2)]
            except Exception as e:   # the chip's compiler refuses the form
                row[f"{what}_refused"] = str(e).splitlines()[0][:300]
        emit(row)


def emit(row):
    os.makedirs(OUT, exist_ok=True)
    line = json.dumps(row)
    print(line, flush=True)
    with open(os.path.join(OUT, "sweep_tiles.jsonl"), "a") as f:
        f.write(line + "\n")


def main(mode, *specs):
    forms = []
    for spec in specs:
        name, _, rest = spec.partition("=")
        fwd, dq, dkv = ([int(n) for n in tile.split("x")]
                        for tile in rest.split(","))
        forms.append((name, {"fwd": fwd, "dq": dq, "dkv": dkv}))
    {"chip": on_chip, "compile": compile_only}[mode](forms)


if __name__ == "__main__":
    main(*sys.argv[1:])
