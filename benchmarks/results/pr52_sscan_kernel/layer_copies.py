"""One Mamba-1 layer, forward and backward, compiled for a DESCRIBED v5e at
the cell's shapes (no chip, ~15 s): every copy / transpose / fusion that
makes a `[8192, 5120]`-sized float32 array and the custom calls, with the
scope each stands under — which layout changes XLA puts between the conv
kernel, the projections and the scan's kernels:
    JAX_PLATFORMS=cpu python3 layer_copies.py [plain]"""
import os, re, sys
os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, os.getcwd())
import jax, jax.numpy as jnp, numpy as np
from jax.experimental import topologies
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from ray_tpu.models import layers as L

jax.config.update("jax_enable_compilation_cache", False)
topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
mesh = Mesh(np.array(topo.devices[:1]).reshape(1), ("dp",))
if sys.argv[1:] == ["plain"]:
    from ray_tpu.ops import selective_scan as ss
    ss._kernel_tiles = lambda *a: 0
cfg = L.Mamba1Config()
d = 2560
on = NamedSharding(mesh, P())
params = jax.tree_util.tree_map(
    lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=on),
    jax.eval_shape(lambda: L.init_mamba1(jax.random.PRNGKey(0), d, cfg)))
u = jax.ShapeDtypeStruct((1, 8192, d), jnp.float32, sharding=on)


def loss(p, u):
    out, y = L.apply_mamba1(p, u, cfg, mesh=mesh)
    return jnp.sum(out * out) + jnp.sum(y)


text = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(params, u).compile().as_text()
big = re.compile(r"f32\[(1,8192,5120|1,5120,8192|1,1024,320,128|1,1024,40,8,128)\]")
for line in text.splitlines():
    m = re.match(r"\s*(?:ROOT )?%([\w.\-]+) = (\S+) (copy|transpose|fusion|custom-call)\(", line)
    if not m or not big.search(m.group(2)):
        continue
    name, shape, kind = m.groups()
    if kind == "fusion" and "copy" not in name and "transpose" not in name:
        continue
    scope = re.search(r'op_name="([^"]*)"', line)
    print(f"{kind:11s} {name:28s} {shape[:60]:60s} {scope.group(1)[-70:] if scope else ''}")
