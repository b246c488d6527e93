"""The rule's kernels compiled for a DESCRIBED v5e at the cell's shapes (no
chip: what Mosaic refuses shows here), each with the seconds to trace, lower
and compile and the custom calls' operand lists:
    JAX_PLATFORMS=cpu python3 compile_kernels.py [block [fwd,bwd]]
A compile is not a chip run."""
import os, sys, time
os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, os.getcwd())
import jax, jax.numpy as jnp
from jax.experimental import topologies
from jax.sharding import SingleDeviceSharding
from ray_tpu.ops import gated_delta as gd

jax.config.update("jax_enable_compilation_cache", False)
block = int(sys.argv[1]) if len(sys.argv) > 1 else gd.BLOCK_TOKENS
which = sys.argv[2].split(",") if len(sys.argv) > 2 else ["fwd", "bwd"]
topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
chip = SingleDeviceSharding(topo.devices[0])
B, T, G, H, K, V, C = 2, 8192, 16, 32, 128, 128, 64
cd = jnp.dtype(jnp.bfloat16)


def shape(*s, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(s, dtype, sharding=chip)


qkv, rows = shape(B, T, 2 * G * K + H * V), shape(B, G, T // C, 8, 128)
kw = dict(k_dim=K, v_dim=V, chunk=C, block=block,
          unrolled=gd.CHUNKS_UNROLLED, cd=cd, normalize=1e-6, interpret=False)
cases = {"fwd": (lambda a, r: gd._delta_fwd(a, r, **kw), (qkv, rows))}
if hasattr(gd, "_delta_bwd"):
    cases["bwd"] = (
        lambda a, r, s, d: gd._delta_bwd(a, r, s, d, **kw),
        (qkv, rows, shape(T // C, B, G, 2, K, V, dtype=cd), shape(B, T, H * V)))
print("block", block, flush=True)
for name in which:
    if name not in cases:
        continue
    fn, args = cases[name]
    t0 = time.time()
    traced = jax.jit(fn).trace(*args)
    t1 = time.time()
    lowered = traced.lower()
    t2 = time.time()
    try:
        compiled = lowered.compile()
    except Exception as e:
        print(name, "REFUSED", str(e)[:3000], flush=True)
        continue
    t3 = time.time()
    text = compiled.as_text()
    calls = [l.strip()[:400] for l in text.splitlines()
             if "tpu_custom_call" in l]
    print(f"{name}: trace {t1-t0:.2f}s lower {t2-t1:.2f}s compile "
          f"{t3-t2:.2f}s, {len(calls)} call(s)", flush=True)
    for c in calls:
        print("  ", c.split("custom_call_target")[0][-300:], flush=True)
    print("  ", compiled.memory_analysis(), flush=True)
