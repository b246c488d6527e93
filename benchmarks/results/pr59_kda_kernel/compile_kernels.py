"""`kda_fwd` and `kda_bwd` compiled for a DESCRIBED v5e at the cell's shapes
(no chip: what Mosaic refuses shows here), each with the seconds to trace,
lower and compile:
    JAX_PLATFORMS=cpu python3 benchmarks/results/pr59_kda_kernel/compile_kernels.py [block]
(`PROBE_MODULE=<file>`: another form of the module, as `rule_probe.py`.)
A compile is not a chip run."""
import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, os.getcwd())
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.experimental import topologies  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

from ray_tpu.ops import kda  # noqa: E402

if os.environ.get("PROBE_MODULE"):
    # another form of the module, from a file (as `rule_probe.py`)
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "kda_probed", os.environ["PROBE_MODULE"])
    kda = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(kda)

jax.config.update("jax_enable_compilation_cache", False)
block = int(sys.argv[1]) if len(sys.argv) > 1 else kda.BLOCK_TOKENS
topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
chip = SingleDeviceSharding(topo.devices[0])
B, T, H, K, V, C = 2, 8192, 32, 128, 128, 64


def f32(*shape):
    return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=chip)


kw = dict(k_dim=K, v_dim=V, chunk=C, block=block,
          cd=jnp.dtype(jnp.bfloat16), normalize=1e-6, interpret=False)
qkv, g = f32(B, T, 3 * H * K), f32(B, T, H * K)
rows = f32(B, H // 2, T // C, 8, 128)
cases = {
    "kda_fwd": (lambda a, b, r: kda._kda_fwd(a, b, r, **kw), (qkv, g, rows)),
    "kda_bwd": (lambda a, b, r, s, d: kda._kda_bwd(a, b, r, s, d, **kw),
                (qkv, g, rows, f32(T // C, B, H // 2, 2, K, V),
                 f32(B, T, H * V))),
}
print("block", block, flush=True)
for name, (fn, args) in cases.items():
    t0 = time.time()
    traced = jax.jit(fn).trace(*args)
    t1 = time.time()
    lowered = traced.lower()
    t2 = time.time()
    try:
        compiled = lowered.compile()
    except Exception as e:  # noqa: BLE001
        print(name, "REFUSED", str(e)[:3000], flush=True)
        continue
    t3 = time.time()
    print(f"{name}: trace {t1 - t0:.2f}s lower {t2 - t1:.2f}s compile "
          f"{t3 - t2:.2f}s", flush=True)
