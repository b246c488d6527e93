"""Sharded training step: the compiled unit every Train worker runs.

Design: a single jitted function over a global mesh — params/opt-state
sharded by the model's logical-axis rules, batch sharded (dp, sp), grads
psum'd implicitly by XLA (dp axis appears in batch but not params), donated
state. The reference's equivalent is the user's torch DDP loop driven by
Ray Train (train/torch/config.py:69 + data_parallel_trainer.py); here the
"backend setup" is just mesh construction — no process groups.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ray_tpu._private import memory_anatomy as _ma
from ray_tpu._private import step_anatomy as _sa
from ray_tpu._private import telemetry as _tm
from ray_tpu.parallel import sharding as sh
from ray_tpu.parallel.compile_watch import (
    OPTIMIZER_SCOPE,
    CompiledFunction,
    configure_compile_cache,
)


def _jit(fn, name: str, **jit_kwargs) -> CompiledFunction:
    """Every compiled unit this module hands out: persistent compile
    cache configured before its first compile, compile observability
    (cache hit/miss counters, compile timing, COMPILE_BEGIN/END events)
    around every call."""
    configure_compile_cache()
    return CompiledFunction(jax.jit(fn, **jit_kwargs), name)


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class TrainState:
    step: jnp.ndarray
    params: Any
    opt_state: Any


def default_optimizer(
    learning_rate: float = 3e-4,
    *,
    warmup_steps: int = 100,
    total_steps: int = 10_000,
    weight_decay: float = 0.1,
    b2: float = 0.95,
    grad_clip: float = 1.0,
) -> optax.GradientTransformation:
    sched = optax.warmup_cosine_decay_schedule(
        0.0, learning_rate, warmup_steps, max(total_steps, warmup_steps + 1)
    )
    return optax.chain(
        optax.clip_by_global_norm(grad_clip),
        optax.adamw(sched, b2=b2, weight_decay=weight_decay),
    )


def make_train_state(
    init_params_fn: Callable[[jax.Array], Any],
    rng: jax.Array,
    optimizer: optax.GradientTransformation,
    mesh: Optional[Mesh] = None,
    param_specs: Any = None,
) -> TrainState:
    """Initialize params + opt state ON-DEVICE with the right shardings:
    params are sharding-constrained inside the jitted init so large models
    never materialize unsharded; opt-state shardings propagate from params
    (mu/nu are zeros_like(params))."""

    def init_fn(rng):
        params = init_params_fn(rng)
        if mesh is not None and param_specs is not None:
            params = jax.tree_util.tree_map(
                lambda x, s: jax.lax.with_sharding_constraint(
                    x, NamedSharding(mesh, s)
                ),
                params,
                param_specs,
            )
        opt_state = optimizer.init(params)
        return TrainState(step=jnp.zeros((), jnp.int32), params=params, opt_state=opt_state)

    state = _jit(init_fn, "train_state_init")(rng)
    _note_state_bytes(state)
    return state


def _note_state_bytes(state: TrainState):
    """Stamp ``ray_tpu_train_state_bytes{kind=params|opt_state,rank}``
    from the deterministic flatten — the exact resident footprint of the
    state this process just materialized (memory-anatomy plane)."""
    if not _tm.ENABLED:
        return
    # the rank the TrainWorker opened its loop with; 0 outside one
    cur = _sa.current()
    rank = cur[1] if cur is not None else 0
    for kind, tree in (("params", state.params),
                       ("opt_state", state.opt_state)):
        leaves, _ = sh.flatten_tree(tree)
        _ma.LEDGER.note_train_state(
            kind, rank, sum(int(l.nbytes) for l in leaves))


# rows over the data-parallel axis, the sequence over `sp`
BATCH_SPEC = P(("dp",), "sp")


def _constrain_batch(batch, mesh: Optional[Mesh], batch_spec: P):
    if mesh is not None:
        batch = jax.tree_util.tree_map(
            lambda x: jax.lax.with_sharding_constraint(
                x, NamedSharding(mesh, batch_spec)
            ),
            batch,
        )
    return batch


def make_train_step(
    loss_fn: Callable[[Any, Any], tuple],
    optimizer: optax.GradientTransformation,
    mesh: Optional[Mesh] = None,
    *,
    batch_spec: P = BATCH_SPEC,
    donate: bool = True,
):
    """loss_fn(params, batch) -> (scalar_loss, metrics_dict).

    Returns jitted step(state, batch) -> (state, metrics): one program,
    gradients reduced over the mesh by the partitioner, the optimizer's
    pass (and the gradient's norm) under the `jax.named_scope`
    ``optimizer``, by which `CompiledFunction.scope_table` tells the step's
    fourth phase and a table worth trusting. The steps whose
    gradients cross hosts over the collective plane are built on this
    module by `ray_tpu.train`.
    """

    def step(state: TrainState, batch):
        batch = _constrain_batch(batch, mesh, batch_spec)
        (loss, metrics), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(state.params, batch)
        metrics = dict(metrics)
        with jax.named_scope(OPTIMIZER_SCOPE):
            updates, opt_state = optimizer.update(
                grads, state.opt_state, state.params)
            params = optax.apply_updates(state.params, updates)
            metrics["grad_norm"] = optax.global_norm(grads)
        return (
            TrainState(step=state.step + 1, params=params,
                       opt_state=opt_state),
            metrics,
        )

    return _jit(step, "train_step",
                donate_argnums=(0,) if donate else ())


def eval_step(loss_fn, mesh: Optional[Mesh] = None, batch_spec: P = BATCH_SPEC):
    def step(params, batch):
        batch = _constrain_batch(batch, mesh, batch_spec)
        _, metrics = loss_fn(params, batch)
        return metrics

    return _jit(step, "eval_step")
