"""A cell's whole train step compiled for a DESCRIBED v5e (no chip), its
optimized HLO dumped for `benchmarks/step_hlo_compare.py`:
    JAX_PLATFORMS=cpu python3 step_program.py <tree> <cell> <dump dir> [flash|auto]
(`attention` forced to "flash" unless "auto" leaves the traffic's own: since
PR 47 the two are one program, on an older tree "auto" holds no flash call).
`<tree>` is a checkout (the program and the benchmark's files are read from
it, so two trees give two dumps). A compile is not a chip run."""
import dataclasses, importlib, math, os, sys, time

tree, cell, dump = sys.argv[1:4]
forced = {} if sys.argv[4:] == ["auto"] else {"attention": "flash"}
sys.path.insert(0, os.path.abspath(tree))
os.chdir(tree)
import jax, jax.numpy as jnp, numpy as np
from jax.experimental import topologies
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from chipbench import catalog
from ray_tpu.parallel.mesh import AXIS_ORDER
from ray_tpu.parallel.train_step import TrainState, default_optimizer, make_train_step

jax.config.update("jax_enable_compilation_cache", False)
manifest = catalog.load_manifest()
resolved = catalog.resolve_cell(manifest, cell, "end_to_end")
traffic = resolved["traffic"]
module_name, preset = resolved["model"]["entry"].split(":")
module = importlib.import_module(module_name)
cfg = dataclasses.replace(getattr(module, preset)(), remat=traffic["remat"],
                          **forced)
axes = {"dp": 1, "tp": 1, **traffic["mesh"]}
n = math.prod(axes.values())
topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
mesh = Mesh(np.array(topo.devices[:n]).reshape(
    tuple(axes.get(a, 1) for a in AXIS_ORDER)), AXIS_ORDER)
opt = default_optimizer(**traffic["optimizer"])
on = lambda spec: NamedSharding(mesh, spec)
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
compiled = step.lower(state, {"tokens": tokens}).compile(compiler_options={
    "xla_dump_to": dump, "xla_dump_hlo_as_text": True,
    "xla_dump_hlo_module_re": "jit_step"})
plan = compiled.memory_analysis()
print(f"{cell}: plan {(plan.argument_size_in_bytes + plan.temp_size_in_bytes) / 1e9:.4f} GB, "
      f"compile {time.time() - t0:.0f}s", flush=True)
