"""The scan's kernels compiled for a DESCRIBED v5e at the cell's shapes (no
chip: what Mosaic refuses shows here), each with the seconds to trace, lower
and compile and the custom calls' operand lists:
    JAX_PLATFORMS=cpu python3 compile_kernels.py [block [fwd,bwd,rule]]
`rule`: the whole op, forward and backward, as the layer calls it. A compile
is not a chip run."""
import os, sys, time
os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, os.getcwd())
import jax, jax.numpy as jnp
from jax.experimental import topologies
from jax.sharding import SingleDeviceSharding
from ray_tpu.ops import selective_scan as ss

jax.config.update("jax_enable_compilation_cache", False)
block = int(sys.argv[1]) if len(sys.argv) > 1 else ss.BLOCK_TOKENS
which = sys.argv[2].split(",") if len(sys.argv) > 2 else ["fwd", "bwd", "rule"]
topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
chip = SingleDeviceSharding(topo.devices[0])
B, T, C, N = 1, 8192, 5120, 16
tiles = ss.BLOCK_TILES
ss.BLOCK_TOKENS = block


def shape(*s):
    return jax.ShapeDtypeStruct(s, jnp.float32, sharding=chip)


inputs = (shape(B, T, C), shape(B, T, C), shape(C, N), shape(B, T, N),
          shape(B, T, N), shape(C), shape(C))
kw = dict(block=block, tiles=tiles, unrolled=ss.TOKENS_A_BODY, interpret=False)
operands = jax.eval_shape(lambda *a: ss._operands(*a, block, tiles), *inputs)
operands = tuple(shape(*o.shape) for o in operands)
starts = shape(B, T // block, C // 128 // tiles, N, tiles, 128)
ss.target = type("T", (), {"where": staticmethod(
    lambda mesh=None, *, interpret=False: ("tpu", 1))})
cases = {
    "fwd": (lambda *a: ss._sscan_fwd(*a, **kw), operands),
    "bwd": (lambda *a: ss._sscan_bwd(*a, **kw),
            operands + (starts, operands[2])),
    "rule": (jax.grad(lambda *a: jnp.sum(ss.selective_scan(*a) ** 2),
                      argnums=tuple(range(7))), inputs),
}
print("block", block, "vmem_bytes", ss._vmem_bytes(block, tiles, N), flush=True)
for name in which:
    fn, args = cases[name]
    t0 = time.time()
    traced = jax.jit(fn).trace(*args)
    t1 = time.time()
    lowered = traced.lower(lowering_platforms=("tpu",))
    t2 = time.time()
    try:
        compiled = lowered.compile()
    except Exception as e:
        print(name, "REFUSED", str(e)[:3000], flush=True)
        continue
    t3 = time.time()
    text = compiled.as_text()
    print(f"{name}: trace {t1-t0:.2f}s lower {t2-t1:.2f}s compile "
          f"{t3-t2:.2f}s", flush=True)
    for l in text.splitlines():
        if any(k in l for k in (" custom-call(", " copy(", " transpose(",
                                "fusion(")) and "ENTRY" not in l:
            print("  ", l.strip().split(", metadata")[0][:230], flush=True)
    print("  ", compiled.memory_analysis(), flush=True)
