"""The rule's forward + backward (`gated_delta_packed` under `jax.grad`)
compiled for a DESCRIBED v5e at the cell's layer (no chip), and the sha256
of the optimized HLO once `benchmarks/step_hlo_compare.py:strip` has taken
out what only says where the source was (each Mosaic kernel's body held to
its MLIR printed without locations):
    JAX_PLATFORMS=cpu python3 benchmarks/results/pr49_delta_kernel/compile_rule.py <out.hlo>
run from the root of each of two trees, says whether a refactor of
`ops/gated_delta.py` left the rule's program as it was (instruction names'
`.N` suffixes renumber with the jaxpr: compare with them taken out,
`sed -E 's/\\.[0-9]+//g'`). A compile is not a chip run."""
import hashlib, os, sys, types
os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, os.getcwd())
sys.path.insert(0, os.path.join(os.getcwd(), "benchmarks"))
import jax, jax.numpy as jnp
from jax.experimental import topologies
from jax.sharding import SingleDeviceSharding
from ray_tpu.ops import gated_delta as gd
import step_hlo_compare
jax.config.update("jax_enable_compilation_cache", False)
topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
chip = SingleDeviceSharding(topo.devices[0])
gd.target = types.SimpleNamespace(where=lambda mesh=None, *, interpret=False: ("tpu", 1))
B, T, G, H, K, V = 2, 8192, 16, 32, 128, 128
f32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32, sharding=chip)
def loss(qkv, g, beta, w):
    return jnp.sum(gd.gated_delta_packed(qkv, g, beta, key_heads=G, k_dim=K, chunk=64, normalize=1e-6) * w)
c = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).trace(f32(B, T, 2*G*K+H*V), f32(B, T, H), f32(B, T, H), f32(B, T, H, V)).lower().compile()
text = step_hlo_compare.strip(c.as_text())
print(len(text), hashlib.sha256(text.encode()).hexdigest())
open(sys.argv[1], "w").write(text)
