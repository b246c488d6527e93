"""Every cell's whole train step at full size for a v5e that is described
and not attached (on-chip-measurement guide, section 2), in two tiers over
ONE construction of the step (`lowered_step`):

- tier 1, a case a cell in every run of the suite: the step is LOWERED.
  What the lowered text can say fails here and costs no chip time: a flash
  kernel gone from the step, state and batch that no longer fit a chip, a
  mesh of another size than the cell's chips, a module laid out over
  another number of devices. Seconds a cell.
- the slow tier (`-m slow`, by name for the cells a PR touches and for a
  new cell before its first chip call): the step is COMPILED, 60–180 s a
  cell. What the chip's compiler would refuse, a memory plan outside the
  floor and the chip's limit, the collectives the partitioner inserts.
  The driver reads the same on the chip in every cell of every PR
  (`train_step.hbm_plan_gb`; a step that does not fit fails its cell).

Neither is a chip run and neither says anything about speed.

All in this one file, the topology described inside a fixture: one process
at a time may load the TPU's library, and under xdist only the worker that
is given this file does.
"""
import dataclasses
import importlib
import math

import pytest

from chipbench import catalog

MANIFEST = catalog.load_manifest()
CELLS = [w["name"] for w in MANIFEST["workloads"]]
HBM_LIMIT = 16.9e9          # bytes_limit a v5e chip reports (PR 21)
MEMORY_FLOOR = 0.25 * 16e9  # the driver refuses a cell that plans less


@pytest.fixture(scope="module")
def topo():
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        described = topologies.get_topology_desc(platform="tpu",
                                                 topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without a chip: keep it out
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield described
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


def lowered_step(topo, cell):
    """`(lowered, arguments, mesh)`: the cell's train step as
    `parallel/train_step.make_train_step` builds it, lowered for the
    described chips from shapes alone, its `(state, batch)` arguments with
    their shardings, and the mesh."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from ray_tpu.parallel.mesh import AXIS_ORDER
    from ray_tpu.parallel.train_step import TrainState, default_optimizer

    resolved = catalog.resolve_cell(MANIFEST, cell, "end_to_end")
    traffic = resolved["traffic"]
    module_name, preset = resolved["model"]["entry"].split(":")
    module = importlib.import_module(module_name)
    # attention="auto" asks the backend, which is the CPU here; on the
    # chip it resolves to the compiled flash kernels, so name them
    assert traffic["attention"] == "auto"
    cfg = dataclasses.replace(getattr(module, preset)(), attention="flash",
                              remat=traffic["remat"])
    axes = {"dp": 1, "tp": 1, **traffic["mesh"]}
    n = math.prod(axes.values())
    assert n == resolved["workload"]["chips"]
    # the program's mesh has every axis; sizes of 1 shard nothing
    shape = tuple(axes.get(a, 1) for a in AXIS_ORDER)
    mesh = Mesh(np.array(topo.devices[:n]).reshape(shape), AXIS_ORDER)
    opt = default_optimizer(**traffic["optimizer"])

    def on(spec):
        return NamedSharding(mesh, spec)

    specs = module.partition_specs(cfg)
    params = jax.tree_util.tree_map(
        lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=on(s)),
        jax.eval_shape(lambda: module.init(jax.random.PRNGKey(0), cfg)),
        specs)
    opt_state = jax.eval_shape(opt.init, params)
    # Adam's moments are laid out as the parameters are; scalars replicate
    param_sharding = {a.shape: a.sharding
                      for a in jax.tree_util.tree_leaves(params)}
    opt_state = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype,
            sharding=param_sharding.get(a.shape, on(P())) if a.ndim
            else on(P())), opt_state)
    state = TrainState(
        step=jax.ShapeDtypeStruct((), jnp.int32, sharding=on(P())),
        params=params, opt_state=opt_state)
    tokens = jax.ShapeDtypeStruct(
        (traffic["batch"], traffic["seq"] + 1), jnp.int32,
        sharding=on(P(("dp",), "sp")))

    def step(state, batch):       # parallel/train_step.make_train_step's
        batch = jax.tree_util.tree_map(
            lambda x: jax.lax.with_sharding_constraint(
                x, on(P(("dp",), "sp"))), batch)
        (_, metrics), grads = jax.value_and_grad(
            lambda p, b: module.loss_fn(p, b, cfg, mesh), has_aux=True)(
                state.params, batch)
        updates, new_opt = opt.update(grads, state.opt_state, state.params)
        new_params = optax.apply_updates(state.params, updates)
        return (TrainState(step=state.step + 1, params=new_params,
                           opt_state=new_opt),
                dict(metrics, grad_norm=optax.global_norm(grads)))

    arguments = (state, {"tokens": tokens})
    return (jax.jit(step, donate_argnums=(0,)).lower(*arguments), arguments,
            mesh)


@pytest.mark.parametrize("cell", CELLS)
def test_cell_step_lowers_for_v5e_and_its_state_fits(topo, cell):
    import jax

    # its mesh is as large as the cell's `chips`, or it raises
    lowered, arguments, mesh = lowered_step(topo, cell)
    # state and batch a chip, by shape, dtype and sharding: what the step
    # holds before its first temporary
    held = sum(math.prod(a.sharding.shard_shape(a.shape)) * a.dtype.itemsize
               for a in jax.tree_util.tree_leaves(arguments))
    text = lowered.as_text()
    print(f"{cell}: arguments {held / 1e9:.2f} GB a chip")
    assert held < HBM_LIMIT, f"{cell} holds {held / 1e9:.2f} GB a chip"
    # forward, dq and dk/dv kernels are in the step: `attention="flash"`
    # resolved to the Pallas kernels, compiled for the TPU
    assert text.count("tpu_custom_call") >= 3
    # the module is laid out over the cell's chips (the collectives the
    # partitioner inserts exist only after a compile: the slow tier's)
    assert f"mhlo.num_partitions = {mesh.size} : i32" in text
    if dict(mesh.shape)["tp"] > 1:
        # the `tp` region's own exchanges are the program's, not the
        # partitioner's
        assert "collective_permute" in text


@pytest.mark.slow
@pytest.mark.parametrize("cell", CELLS)
def test_cell_step_compiles_for_v5e_and_fits(topo, cell):
    lowered, _, mesh = lowered_step(topo, cell)
    compiled = lowered.compile()
    plan = compiled.memory_analysis()
    held = plan.argument_size_in_bytes + plan.temp_size_in_bytes
    hlo = compiled.as_text()
    print(f"{cell}: plan {held / 1e9:.2f} GB a chip")
    assert MEMORY_FLOOR < held < HBM_LIMIT, \
        f"{cell} plans {held / 1e9:.2f} GB a chip"
    # forward, dq and dk/dv kernels are in the step, and nothing gathers
    # the flash operands (PR 21)
    assert hlo.count('custom_call_target="tpu_custom_call"') >= 3
    if mesh.size > 1:
        assert "all-reduce" in hlo
        assert " all-gather(" not in hlo
    else:
        assert "all-reduce" not in hlo
