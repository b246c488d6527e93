"""The three cells' whole train steps, compiled at full size for a v5e
that is described and not attached (on-chip-measurement guide, section 2):
what the chip's compiler would refuse, a cell that no longer fits the
chip's memory, or a kernel that is gone from the step, fails here and costs
no chip time. A compile is not a chip run and says nothing about speed.

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


@pytest.mark.parametrize("cell", [w["name"] for w in MANIFEST["workloads"]])
def test_cell_step_compiles_for_v5e_and_fits(topo, cell):
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

    compiled = jax.jit(step, donate_argnums=(0,)).lower(
        state, {"tokens": tokens}).compile()
    plan = compiled.memory_analysis()
    held = plan.argument_size_in_bytes + plan.temp_size_in_bytes
    print(f"{cell}: plan {held / 1e9:.2f} GB a chip")
    assert MEMORY_FLOOR < held < HBM_LIMIT, \
        f"{cell} plans {held / 1e9:.2f} GB a chip"
    hlo = compiled.as_text()
    # forward, dq and dk/dv kernels are in the step, and nothing gathers
    # the flash operands (PR 21)
    assert hlo.count('custom_call_target="tpu_custom_call"') >= 3
    if n > 1:
        assert "all-reduce" in hlo
        assert " all-gather(" not in hlo
    else:
        assert "all-reduce" not in hlo
